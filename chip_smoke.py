#!/usr/bin/env python3
"""Chip smoke: the replay engine and the live gateway on one TPU, at
city-fleet size, checked against the plain scan reference.

    python3 chip_smoke.py              # one chip: phases (a)-(e)
    python3 chip_smoke.py --chips 4    # four chips: the sharded fleet only

Phases (one process; each prints its wall seconds, compiles included,
what compiling took within it, and the device's peak bytes in use):

  (a) device: platform, kind, count, kernel mode, compile cache.
  (b) ``simulate_service(engine="chunked", materialize=False)`` at
      N = 2^20, T = 256, K = 1: the compiled engine must hold the Pallas
      kernel (``tpu_custom_call``), and over its first 64 slots it must
      match the ``fleet.simulate`` scan reference run on the chip (the
      checks are listed below).
  (c) the same at K = 1024 under a streaming mobility walk (T = 128).
  (d) the live gateway (``GatewayCore`` + ``run_pipelined_loop``) at
      N = 2^20: its decisions must be bit-identical to batch replay.

With ``--chips 4``, phases 4a-4c only: ``simulate_service(engine=
"sharded")`` at N = 2^21 over a 1-D mesh of the four chips against the
one-chip chunked engine, and a mesh-sharded gateway against a one-chip
gateway, bit for bit.

The last line of standard output is the JSON result; it is printed only
when every phase passed on a TPU.  Without a TPU the script exits 1.
"""

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_CITY = 2**20  # one chip's city fleet
N_MESH = 2**21  # the same fleet over four chips
SLAB = CHUNK = 64  # slab == chunk keeps the kernel's slot streams compact
PREFIX = 64  # slots checked against the scan reference
K_METRO = 1024
WAVES = 48
MESH_WAVES = 16
SEED = 7

# Over the first PREFIX slots the engine is held to:
#  - exactness where no rounding enters: the per-slot task counts and the
#    final visit counts equal the scan reference's (XLA) bit for bit (a
#    stale state tile would lose visits); the engine, the kernel on the
#    materialized prefix in one go, and the same one slot at a time
#    (resuming from its own state) end in the same state bit for bit;
#  - decisions: every decision the kernel (Mosaic) took is the one its own
#    duals entering that slot imply, w - (lam o + mu h) > 0, except where
#    that margin is within EPS of the price's scale (the two round the
#    price differently, within a few ulp).  So a decision that differs
#    from the scan's is one where the engine's and the scan's duals put
#    the device on opposite sides of its threshold: the dual drift at
#    that device-slot reaches across it.  Their sums, divisions and powers
#    round differently, and the threshold policy turns a difference at a
#    near-tie of the policy into a step in that device's lam;
#  - the capacity dual mu (with K cloudlets, their mean):
#    tests/test_kernels.py's rtol, with an atol scaled to the series' own
#    magnitude.
# The series that follow from the decisions (counts of offloads and
# admits, load, reward, power), the lam norm and the per-cloudlet duals
# are printed: each cloudlet's dual answers to its ~N/K devices, so one
# device's policy flipping at a near-tie moves it by ~K/N relative.
RTOL = 1e-5
EPS = 1e-6  # ~8 ulp of fp32
SHOWN_KEYS = ("mu_k", "lam_norm", "offloads", "admits", "correct", "load",
              "reward", "power")


def log(msg):
    print(msg, flush=True)


def peak_bytes():
    import jax
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()]


_COMPILE = {"backend s": 0.0, "trace+lower s": 0.0, "cache hits": 0,
            "cache misses": 0}


def count_compiles():
    """Accumulate JAX's compile events into ``_COMPILE`` (the backend
    compile time of a cache hit is the time to load the executable)."""
    import jax

    def on_time(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILE["backend s"] += secs
        elif event in ("/jax/core/compile/jaxpr_trace_duration",
                       "/jax/core/compile/jaxpr_to_mlir_module_duration"):
            _COMPILE["trace+lower s"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            _COMPILE["cache hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            _COMPILE["cache misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_time)
    jax.monitoring.register_event_listener(on_event)


class Phase:
    """Times a phase and reports, after it, the devices' peak bytes and
    what compiling took within it."""

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        log(f"== phase {self.name}")
        self.t = time.perf_counter()
        self.c0 = dict(_COMPILE)
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            gc.collect()
            c = {k: v - self.c0[k] for k, v in _COMPILE.items()}
            log(f"   {self.name}: {time.perf_counter() - self.t:.2f} s, "
                f"peak_bytes_in_use {peak_bytes()}; compiling: backend "
                f"{c['backend s']:.2f} s, trace+lower "
                f"{c['trace+lower s']:.2f} s, cache hits "
                f"{c['cache hits']}, misses {c['cache misses']}")


def exact(name, got, want):
    """Raises unless ``got`` equals ``want`` bit for bit."""
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    n_bad = int(np.sum(got != want))
    log(f"   {name}: " + ("bit-identical" if n_bad == 0 else
                          f"{n_bad} of {want.size} entries differ"))
    if n_bad:
        raise AssertionError(f"{name} differs")


def compare_series(got, ref, slots):
    """Per-slot series of an engine run against the scan reference over
    its first ``slots`` slots: tasks exactly, the capacity dual ``mu``
    within RTOL (atol RTOL x its largest reference value); the other
    series are shown.  Logs, per key, the slots that differ and the
    largest absolute and relative differences."""
    import numpy as np
    bad = []
    for k in ("tasks", "mu") + SHOWN_KEYS:
        if k not in ref:
            continue
        g = np.asarray(got[k], np.float64)[:slots]
        r = np.asarray(ref[k], np.float64)[:slots]
        diff = np.abs(g - r)
        atol = RTOL * np.abs(r).max()
        den = np.maximum(np.abs(r), atol)
        rel = np.divide(diff, den, out=np.zeros_like(diff), where=den > 0)
        log(f"   {k:9s}: {int(np.sum(diff.reshape(slots, -1).max(1) > 0))}"
            f"/{slots} slots differ, max |diff| {diff.max():.6g}, "
            f"max rel {rel.max():.3g}")
        if k == "tasks" and not np.array_equal(g, r):
            bad.append(k)
        if k == "mu" and not np.allclose(g, r, rtol=RTOL, atol=atol):
            bad.append(k)
    if bad:
        raise AssertionError(f"series outside tolerance: {bad}")


def exact_state(name, got, want):
    """``exact`` over an OnAlgoState's lam, mu and visit counts."""
    for part, g, w in (("lam", got.lam, want.lam), ("mu", got.mu, want.mu),
                       ("visit counts", got.rho.counts, want.rho.counts)):
        exact(f"{name}: {part}", g, w)


def check_decisions(dec, lam_in, mu_in, ref, cs, topology):
    """The kernel's per-device decisions (``dec``) against the margins its
    own duals entering each slot (``lam_in`` (T, N), ``mu_in`` (T,) or
    (T, K)) imply, and against the scan's decisions and margins (``ref``,
    collected with ``collect_decisions``)."""
    import jax
    import jax.numpy as jnp
    from repro.core import onalgo

    @jax.jit  # one fused pass: no (T, N) temporaries held at once
    def stats(off, off_ref, m_ref, lam_in, mu_in, trace, ov, params,
              assoc):
        margin = jax.vmap(
            lambda lam, mu, o, h, w, a: onalgo.decision_margin(
                onalgo.OnAlgoState(lam=lam, mu=mu, rho=None), o, h, w,
                params, a),
            in_axes=(0, 0, 0, 0, 0, None if assoc is None else 0))(
                lam_in, mu_in, ov.o, ov.h, ov.w, assoc)
        live = (ov.w > 0) & (trace.j_idx > 0)
        scale = jnp.abs(ov.w) + jnp.abs(ov.w - margin)  # |w| + price
        tie = jnp.abs(margin) <= EPS * scale
        own = off != ((margin > 0) & live)
        flips = off != off_ref
        across = jnp.abs(m_ref) <= jnp.abs(margin - m_ref) + EPS * scale
        drift = jnp.where(live, jnp.abs(margin - m_ref) / scale, jnp.nan)
        return {
            "live": jnp.sum(live), "own": jnp.sum(own),
            "own_beyond": jnp.sum(own & ~tie), "flips": jnp.sum(flips),
            "flips_slot": jnp.max(jnp.sum(flips, axis=1)),
            "unexplained": jnp.sum(flips & ~across),
            "tie_at_flips": jnp.max(jnp.where(
                flips, jnp.abs(m_ref) / scale, 0.0)),
            "drift_median": jnp.nanmedian(drift),
            "drift_max": jnp.nanmax(drift),
        }

    r = {k: float(v) for k, v in stats(
        dec["offload_mask"], ref["offload_mask"], ref["offload_margin"],
        lam_in, mu_in, cs.trace, cs.overlay, cs.params,
        None if topology is None else topology.assoc_at(
            0, cs.overlay.o.shape[0])).items()}
    log(f"   decisions vs the kernel's own duals: {r['own']:.0f} of "
        f"{r['live']:.0f} live device-slots differ, "
        f"{r['own_beyond']:.0f} of them beyond EPS {EPS:g} of a tie")
    log(f"   decisions vs the scan: {r['flips']:.0f} differ (at most "
        f"{r['flips_slot']:.0f} a slot), {r['unexplained']:.0f} where the "
        f"dual drift does not reach across the threshold; scan margin / "
        f"price at them: max {r['tie_at_flips']:.3g}; dual drift at live "
        f"device-slots (|d price| / price): median "
        f"{r['drift_median']:.3g}, max {r['drift_max']:.3g}")
    if r["own_beyond"] or r["unexplained"]:
        raise AssertionError("decisions the duals do not explain")


def engine_hlo(ss, topology):
    """Compiled text of the streaming engine's fused slab step, lowered
    as ``simulate_service`` runs it at this size."""
    import jax
    import jax.numpy as jnp
    from repro.core import fleet

    N, M = ss.sim.num_devices, ss.tables[0].shape[-1]
    K = None if topology is None else topology.K
    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    carry = (f32((N,)), f32(() if K is None else (K,)), f32((N, M)),
             jax.eval_shape(lambda: fleet._stream_series_buffers(
                 ss.sim.T, topology, True)))
    lowered = fleet._pipelined_slab_step.lower(
        carry, jnp.int32(0), jnp.int32(0), ss.tables, ss.params, ss.rule,
        topology, src=fleet._StaticSource(ss.slab_aligned), L=SLAB,
        chunk=CHUNK, block_n=None, enforce_slot_capacity=True,
        topo_binned=None)
    return lowered.compile().as_text()


def replay(N, T, prefix, topology=None, check_hlo=True):
    """Phases (b)/(c): the streaming chunked engine through
    ``simulate_service``; then, over the first ``prefix`` slots, the
    engine and the same kernel on the materialized prefix (in one go and
    one slot at a time) against each other and the scan reference (see
    the checks above)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import fleet, onalgo
    from repro.serve.compile import compile_service, compile_service_streaming
    from repro.serve.simulator import (SimConfig, simulate_service,
                                       synthetic_pool)

    pool = synthetic_pool()
    sim = SimConfig(num_devices=N, T=T, algo="onalgo", B_n=0.06,
                    H=N * 441e6 / 8, seed=SEED)
    t = time.perf_counter()
    metrics = simulate_service(sim, pool, engine="chunked",
                               materialize=False, chunk=CHUNK, slab=SLAB,
                               topology=topology)
    log(f"   simulate_service(chunked, streaming): "
        f"{time.perf_counter() - t:.2f} s; " + ", ".join(
            f"{k}={v:.6g}" for k, v in metrics.items()))
    if not all(np.isfinite(v) for v in metrics.values()):
        raise AssertionError(f"non-finite metrics {metrics}")

    ss = compile_service_streaming(sim, pool)
    if check_hlo:
        hlo = engine_hlo(ss, topology)
        if "tpu_custom_call" not in hlo:
            raise AssertionError("no tpu_custom_call in the compiled engine")
        log("   compiled engine holds tpu_custom_call (native kernel)")
    # the engine again, over the prefix, as simulate_service runs it
    series, fin_eng = fleet.simulate_chunked_stream(
        ss.slab, prefix, N, ss.tables, ss.params, ss.rule, chunk=CHUNK,
        slab=SLAB, enforce_slot_capacity=True, topology=topology,
        source_aligned=ss.slab_aligned)
    series = {k: np.asarray(v) for k, v in series.items()}
    del ss
    gc.collect()

    cs = compile_service(SimConfig(**{**sim.__dict__, "T": prefix}), pool)
    topo_p = None if topology is None else topology.prefix(prefix)
    log(f"   first {prefix} slots:")
    # the same kernel on the materialized prefix: in one go, for its
    # decisions, and one slot at a time (resuming from its own state),
    # for the duals entering every slot
    dec, fin = fleet.simulate_chunked(*cs.simulate_args(), cs.rule,
                                      chunk=CHUNK, overlay=cs.overlay,
                                      topology=topo_p,
                                      collect_decisions=True)
    exact_state("materialized prefix vs the engine", fin, fin_eng)
    assoc = None if topo_p is None else topo_p.assoc_at(0, prefix)
    st = onalgo.init_state(N, cs.tables[0].shape[-1],
                           K=None if topology is None else topology.K)
    lam_in, mu_in = [], []
    for t in range(prefix):
        lam_in.append(st.lam)
        mu_in.append(st.mu)
        at_t = lambda x: x[t:t + 1]
        _, st = fleet.simulate_chunked(
            jax.tree.map(at_t, cs.trace), cs.tables, cs.params, cs.rule,
            chunk=1, overlay=jax.tree.map(at_t, cs.overlay),
            topology=(None if topo_p is None else dataclasses.replace(
                topo_p, assoc=at_t(assoc))), t0=t, state0=st)
    lam_in, mu_in = jnp.stack(lam_in), jnp.stack(mu_in)
    exact_state("one slot at a time vs in one go", st, fin)
    del fin, st
    ref, fin_ref = fleet.simulate(*cs.simulate_args(), cs.rule,
                                  algo="onalgo", overlay=cs.overlay,
                                  enforce_slot_capacity=True,
                                  topology=topo_p, collect_decisions=True)
    log("   against the fleet.simulate scan:")
    exact("final visit counts", fin_eng.rho.counts, fin_ref.rho.counts)
    compare_series(series, ref, prefix)
    check_decisions(dec, lam_in, mu_in, ref, cs, topo_p)
    return metrics


def gateway_masks(replies, lg, T, N):
    import numpy as np
    off = np.zeros((T, N), bool)
    adm = np.zeros_like(off)
    for t, r in enumerate(replies):
        wv = lg.wave(t)
        off[t, wv.idx] = r.offload
        adm[t, wv.idx] = r.admitted
    return off, adm


def live_gateway(N, waves):
    """Phase (d): the pipelined live gateway against batch replay."""
    import numpy as np
    from repro.core import fleet
    from repro.serve.compile import compile_service, compile_service_streaming
    from repro.serve.gateway import GatewayCore, run_pipelined_loop
    from repro.serve.simulator import SimConfig, synthetic_pool
    from repro.workload.loadgen import ServiceLoadGen

    pool = synthetic_pool()
    sim = SimConfig(num_devices=N, T=waves, algo="onalgo", B_n=0.06,
                    H=N * 441e6 / 8, seed=SEED)
    ss = compile_service_streaming(sim, pool)
    core = GatewayCore.for_service(ss)
    lg = ServiceLoadGen(ss, prefetch=True)
    t = time.perf_counter()
    warmed = core.warmup(n_reports=[lg.wave(t).size for t in range(waves)])
    log(f"   warmup: buckets {warmed} compiled in "
        f"{time.perf_counter() - t:.2f} s")
    t = time.perf_counter()
    replies, stats = run_pipelined_loop(core, lg, 0, waves, max_in_flight=2,
                                        slo_ms=60_000.0)
    s = stats.summary()
    log(f"   served {s['waves']} waves ({s['reports']} reports) in "
        f"{time.perf_counter() - t:.2f} s; p50 {s['p50_ms']:.3f} ms, "
        f"p99 {s['p99_ms']:.3f} ms, fallback waves {s['fallback_waves']}")
    if s["waves"] < waves or s["fallback_waves"]:
        raise AssertionError(f"gateway served {s}")
    off, adm = gateway_masks(replies, lg, waves, N)
    del replies, lg, core, ss
    gc.collect()
    cs = compile_service(sim, pool)
    ref, _ = fleet.simulate(cs.trace, cs.tables, cs.params, cs.rule,
                            algo="onalgo", overlay=cs.overlay,
                            enforce_slot_capacity=True,
                            collect_decisions=True)
    ok_off = np.array_equal(off, np.asarray(ref["offload_mask"]))
    ok_adm = np.array_equal(adm, np.asarray(ref["admit_mask"]))
    if not (ok_off and ok_adm):
        raise AssertionError(
            "gateway decisions differ from batch replay: "
            f"{int(np.sum(off != np.asarray(ref['offload_mask'])))} offload, "
            f"{int(np.sum(adm != np.asarray(ref['admit_mask'])))} admit")
    log(f"   {waves} waves x {N} devices: decisions bit-identical to "
        f"fleet.simulate(collect_decisions=True) replay "
        f"({int(off.sum())} offloads, {int(adm.sum())} admits)")


def four_chips(N, T, n_gateway, waves):
    """--chips 4: the fleet sharded over a 1-D mesh of the four chips
    against the one-chip engine and gateway on device 0, as three timed
    phases.  The gateway pair serves phase (d)'s fleet (``n_gateway``
    devices, the same programs), ``waves`` waves of it."""
    import numpy as np
    from repro.parallel.mesh import make_fleet_mesh
    from repro.serve.compile import compile_service_streaming
    from repro.serve.gateway import GatewayCore
    from repro.serve.simulator import (SimConfig, simulate_service,
                                       synthetic_pool)
    from repro.workload.loadgen import ServiceLoadGen

    mesh = make_fleet_mesh()
    log(f"   mesh {dict(mesh.shape)} over {[d.id for d in mesh.devices]}")
    pool = synthetic_pool()
    sim = SimConfig(num_devices=N, T=T, algo="onalgo", B_n=0.06,
                    H=N * 441e6 / 8, seed=SEED)
    with Phase(f"4a: simulate_service(sharded) N={N} T={T}, shard-local "
               "generation"):
        m_mesh = simulate_service(sim, pool, engine="sharded",
                                  materialize=False, mesh=mesh, slab=SLAB)
        log("   " + ", ".join(f"{k}={v:.6g}" for k, v in m_mesh.items()))
    with Phase(f"4b: simulate_service(chunked) N={N} T={T} on device 0"):
        m_one = simulate_service(sim, pool, engine="chunked",
                                 materialize=False, chunk=CHUNK, slab=SLAB)
        if m_mesh["tasks"] != m_one["tasks"]:
            raise AssertionError(f"tasks {m_mesh['tasks']} != "
                                 f"{m_one['tasks']}")
        worst = max(abs(m_mesh[k] - v) / abs(v) for k, v in m_one.items())
        log(f"   sharded vs one-chip chunked engine: max rel diff "
            f"{worst:.3g} over {sorted(m_one)}")
        for k, v in m_one.items():
            np.testing.assert_allclose(m_mesh[k], v, rtol=RTOL, err_msg=k)

    with Phase(f"4c: mesh gateway vs one-chip gateway, N={n_gateway}, "
               f"{waves} waves"):
        sim_w = SimConfig(**{**sim.__dict__, "num_devices": n_gateway,
                             "T": WAVES})
        ss = compile_service_streaming(sim_w, pool)
        one = GatewayCore.for_service(ss)
        sh = GatewayCore.for_service(ss, mesh=mesh)
        t = time.perf_counter()
        for wv in ServiceLoadGen(ss).waves(0, waves):
            o1, a1 = one.tick(wv.idx, wv.o, wv.h, wv.w)
            o4, a4 = sh.tick(wv.idx, wv.o, wv.h, wv.w)
            if not (np.array_equal(o1, o4) and np.array_equal(a1, a4)):
                raise AssertionError(f"mesh gateway differs at wave {wv.t}")
            log(f"   wave {wv.t}: {wv.size} reports, decisions equal "
                f"({time.perf_counter() - t:.2f} s)")
        st1, st4 = one.state, sh.state
        for name, a, b in (("lam", st1.lam, st4.lam),
                           ("mu", st1.mu, st4.mu),
                           ("counts", st1.rho.counts, st4.rho.counts)):
            if not np.array_equal(np.asarray(a), np.asarray(b)):
                raise AssertionError(f"mesh gateway state {name} differs")
        log(f"   mesh gateway: {waves} waves and final state bit-identical "
            "to the one-chip core")
        for name, x in (("lam", st4.lam), ("mu", st4.mu),
                        ("counts", st4.rho.counts), ("t", st4.rho.t)):
            log(f"   state {name} {x.shape}: {x.sharding}, devices "
                f"{sorted(d.id for d in x.sharding.device_set)}")
        for x in (st4.lam, st4.rho.counts):
            n_dev = len(x.sharding.device_set)
            if (n_dev != len(mesh.devices.flat)
                    or x.sharding.is_fully_replicated):
                raise AssertionError(f"state not split over the mesh: "
                                     f"{x.sharding}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    import jax
    from repro.compile_cache import enable_compile_cache
    from repro.kernels.ops import interpret_mode

    cache = enable_compile_cache()
    count_compiles()
    with Phase("a: device"):
        devs = jax.devices()
        d = devs[0]
        log(f"   platform {d.platform}, device_kind {d.device_kind}, "
            f"count {len(devs)}, interpret_mode()={interpret_mode()}, "
            f"compile cache {cache}")
        if d.platform != "tpu":
            log("no TPU: this smoke runs only on the chip")
            return 1
        if interpret_mode():
            raise AssertionError("kernels would run interpreted on a TPU")
        if len(devs) < args.chips:
            raise AssertionError(f"--chips {args.chips} but {len(devs)} "
                                 "devices")

    t_all = time.perf_counter()
    if args.chips == 4:
        four_chips(N_MESH, 128, N_CITY, MESH_WAVES)
        count = 4
    else:
        with Phase("b: replay engine, K=1"):
            replay(N_CITY, 256, PREFIX)
        with Phase("c: replay engine, K=1024 mobility walk"):
            from repro.topology import Topology
            topo = Topology.mobility_walk(K_METRO, N_CITY, 128,
                                          H=N_CITY * 441e6 / 8,
                                          p_handover=0.05, seed=SEED,
                                          streaming=True)
            replay(N_CITY, 128, PREFIX, topology=topo)
        with Phase("d: live gateway"):
            live_gateway(N_CITY, WAVES)
        count = 1
    log(f"== e: all phases passed in {time.perf_counter() - t_all:.2f} s, "
        f"peak_bytes_in_use {peak_bytes()}")
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
