"""End-to-end edge-analytics simulator: the paper's testbed in software.

Fleet of N camera devices -> local classifier + gain predictor -> offloading
policy (OnAlgo or a baseline) -> cloudlet classifier for admitted tasks.
Uses the synthetic datasets with *trained* classifier pairs, the paper's
measured power curve p(rate) and cycle statistics, and bursty traffic.

This is the substrate behind benchmarks/bench_fig5..8.  ``simulate_service``
is a thin wrapper over the vectorized fleet engine: serve/compile.py lowers
the run to the core ``(Trace, tables, params, overlay)`` contract and the
selected engine rolls the whole horizon.  With ``materialize=False`` the
lowering is streaming — workload slabs are generated on device inside the
engine loop, so fleet size is bounded by compute, not by (T, N) arrays.

The original per-slot Python loop (and its v0 host RNG contract) is gone;
its metrics stay pinned by tests/golden/service_legacy_fig5.json via the
frozen sampler in tests/legacy_workload.py.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core.fleet import simulate
from repro.core.state_space import StateSpace
from repro.data.predictor import GainPredictor, calibrate
from repro.data.synthetic import ClassifierPair, Dataset, build_scenario

RATES = np.array([10.0, 25.0, 40.0])  # Mbps (testbed operating points)


def power_of_rate(r):
    """Paper Fig. 2b fitted curve (Watts)."""
    return -0.00037 * r**2 + 0.0214 * r + 0.1277


@dataclasses.dataclass
class SimConfig:
    num_devices: int = 4
    T: int = 2000
    B_n: float = 0.08  # W average power budget
    H: float = 2 * 441e6  # cycles/slot cloudlet capacity
    v_risk: float = 0.5  # risk aversion v_n in eq. (1)
    burst_len: tuple = (5, 10)
    mean_gap: float = 8.0
    seed: int = 0
    algo: str = "onalgo"  # onalgo | ato | rco | ocos | local | cloud
    ato_theta: float = 0.85
    step_a: float = 0.5
    num_w_levels: int = 8
    zeta: float = 0.0  # P3 delay weight (0 = accuracy only)
    # workload RNG contract (see repro.workload): 1 = counter-based
    # streams, the only live contract (0, the legacy host draw order, is
    # retired — tests/golden pins its metrics via a frozen test sampler)
    rng_version: int = 1
    # paper-measured delays (seconds)
    d_tr: float = 0.157e-3
    d_pr_cloud: float = 0.191e-3
    d_pr_dev: float = 2.537e-3


@dataclasses.dataclass
class PrecomputedPool:
    """Per-test-image precomputations shared across slots/devices."""

    local_correct: np.ndarray  # (S,)
    cloud_correct: np.ndarray  # (S,)
    d_local: np.ndarray  # (S,) local top-1 confidence
    phi_hat: np.ndarray  # (S,) predicted gain
    sigma: np.ndarray  # (S,) predictor confidence
    cycles: np.ndarray  # (S,) cloudlet cycles per image


def pool_fingerprint(pool: "PrecomputedPool") -> tuple:
    """Content hash of the pool arrays — the key guarding the per-pool
    caches (the calibrated space in ``pool_space``, the device copies in
    ``serve.compile``), so in-place recalibration of a pool can never
    serve stale data."""
    return tuple(hash(np.asarray(x).tobytes())
                 for x in (pool.cycles, pool.phi_hat, pool.sigma,
                           pool.d_local, pool.local_correct,
                           pool.cloud_correct))


def build_pool(data: Dataset, pair: ClassifierPair,
               predictor: GainPredictor, seed: int = 0) -> PrecomputedPool:
    rng = np.random.default_rng(seed)
    xt = jnp.asarray(data.x_test)
    lp = np.asarray(pair.local_probs(xt))
    cp = np.asarray(pair.cloud_probs(xt))
    y = data.y_test
    phi, sigma = predictor.predict(lp)
    cycles = np.clip(rng.normal(441e6, 90e6, len(y)), 150e6, None)
    return PrecomputedPool(
        local_correct=(lp.argmax(-1) == y).astype(np.float64),
        cloud_correct=(cp.argmax(-1) == y).astype(np.float64),
        d_local=lp.max(-1),
        phi_hat=phi, sigma=sigma, cycles=cycles)


def calibrated_space(phi_hat: np.ndarray, sigma: np.ndarray,
                     num_w: int = 8, v_risk: float = 0.5) -> StateSpace:
    """State space calibrated to a per-image gain-table pair.

    The w grid must COVER the realized gain distribution (paper footnote
    5: granularity): a saturated top level makes the dual estimator
    undercount high-gain offloads and the power constraint then
    equilibrates ~25% above budget.  This is the uncached body of
    :func:`pool_space`; the gain tier (:mod:`repro.gain`) calls it
    directly to calibrate a space to a model-predicted table pair —
    float64 in, so a model frozen back into a pool via
    ``to_pool_tables()`` re-derives the identical space.
    """
    w_all = np.clip(np.asarray(phi_hat, np.float64)
                    - v_risk * np.asarray(sigma, np.float64), 0.0, 1.0)
    w_hi = max(float(np.quantile(w_all, 0.999)), 0.1)
    return StateSpace(
        o_levels=tuple(power_of_rate(RATES).tolist()),
        h_levels=(441e6 - 90e6, 441e6, 441e6 + 90e6),
        w_levels=tuple(np.linspace(0.0, w_hi, num_w).tolist()),
    )


def pool_space(pool: "PrecomputedPool", num_w: int = 8,
               v_risk: float = 0.5) -> StateSpace:
    """Pool-calibrated quantized state space (single source of truth).

    Cached per (num_w, v_risk) on the pool object (compile_service calls
    this once per run), keyed by the pool's content fingerprint so
    in-place recalibration invalidates.  See :func:`calibrated_space`
    for the calibration rule itself.
    """
    fp = pool_fingerprint(pool)
    cache = getattr(pool, "_space_cache", None)
    if cache is None or cache[0] != fp:
        cache = pool._space_cache = (fp, {})
    cache = cache[1]
    key = (num_w, v_risk)
    if key not in cache:
        cache[key] = calibrated_space(pool.phi_hat, pool.sigma,
                                      num_w=num_w, v_risk=v_risk)
    return cache[key]


def make_scenario(kind: str, seed: int = 0):
    """(data, pair, predictor, pool) for 'easy' (MNIST-like) or 'hard'."""
    data, pair = build_scenario(kind, seed=seed)
    predictor = calibrate(pair, data.x_train[:5000], data.y_train[:5000])
    pool = build_pool(data, pair, predictor, seed=seed)
    return data, pair, predictor, pool


def synthetic_pool(S: int = 64, seed: int = 0) -> PrecomputedPool:
    """A deterministic synthetic pool — no classifier training needed.

    Used by the fast tests, the golden legacy fixture, and the
    compile-path benchmarks: statistics mimic an easy/hard blend (local
    ~60% right, cloudlet ~85%, modest predicted gains)."""
    rng = np.random.default_rng(seed)
    return PrecomputedPool(
        local_correct=(rng.random(S) < 0.6).astype(np.float64),
        cloud_correct=(rng.random(S) < 0.85).astype(np.float64),
        d_local=rng.uniform(0.3, 1.0, S),
        phi_hat=rng.uniform(0.0, 0.3, S),
        sigma=rng.uniform(0.0, 0.1, S),
        cycles=np.clip(rng.normal(441e6, 90e6, S), 150e6, None))


def simulate_service(sim: SimConfig, pool: PrecomputedPool,
                     on: Optional[np.ndarray] = None, *,
                     engine: str = "scan", chunk: int = 16,
                     block_n: Optional[int] = None, mesh=None,
                     device_axis: str = "data", materialize: bool = True,
                     slab: Optional[int] = None, topology=None,
                     topo_binned: Optional[bool] = None,
                     pipelined: Optional[bool] = None,
                     gain_source=None) -> dict:
    """Run T slots of the service; returns aggregate metrics.

    Accounting follows the paper's comparison protocol (Sec. VI.C.2):
    power is consumed on transmission; accuracy comes from the cloudlet
    only for admitted tasks (per-slot capacity enforced for every policy);
    non-offloaded / dropped tasks score the local classifier's result.

    The run is compiled to the fleet contract (serve/compile.py) and
    rolled through the selected fleet engine on the same compiled
    workload — all engines produce identical metrics:

      engine="scan"     ``fleet.simulate``: one scanned rollout, any algo.
      engine="chunked"  ``fleet.simulate_chunked``: the fused Pallas
                        kernels (``block_n`` routes device-tiled);
                        onalgo / local / cloud.
      engine="sharded"  ``fleet.simulate_sharded`` over ``mesh`` (default:
                        a 1-axis mesh over all local devices); N must be
                        a multiple of the ``device_axis`` shard count.

    ``materialize=False`` switches the chunked/sharded engines to the
    STREAMING lowering (``compile_service_streaming``): no (T, N) trace
    or overlay is ever built — each ``slab`` (default 16 * chunk) slots
    of workload are generated on device from counters inside the engine
    loop and dropped after their accounting folds, so peak memory is
    O(slab * N) independent of the horizon and metrics are identical to
    the materialized path (counter streams are slab-invariant).  The
    scan engine and arrival overrides need materialized arrays.

    ``on``: optional (T, N) bool arrival matrix overriding the built-in
    bursty traffic — e.g. ``CompiledScenario.task_mask()`` from the
    scenario engine, so the service tier replays the same workloads as
    the fleet simulator.

    ``topology``: optional multi-cloudlet :class:`~repro.topology.Topology`
    — the capacity dual becomes a (K,) vector (each device priced by its
    current cloudlet) and per-slot admission runs per cloudlet under
    H_k.  ``Topology.uniform(K=1, N, sim.H)`` reproduces the scalar path
    bit for bit on every engine.  Build it with total capacity ``sim.H``
    (the builders split it over cloudlets) so the dual preconditioner
    and the K = 1 path stay consistent.

    ``topo_binned``: reduction layout for the chunked kernels' in-kernel
    per-cloudlet gathers/scatters (None = auto by K; see
    ``fleet.simulate_chunked``).  Scan/sharded engines ignore it.

    ``pipelined``: streaming engines only (``materialize=False``) —
    route the slab walk through the pipelined runtime (fused launches,
    donated carries, device-resident accounting; default automatic at
    N >= 65536, bit-identical either way).  The chunked stream also
    gets the block-aligned slab source (one fewer covering uniform
    block generated per slab).

    ``gain_source``: optional :class:`~repro.gain.GainSource` selecting
    where the per-image offloading-gain estimate comes from —
    ``TableGain()`` (the pool's phi_hat/sigma tables; the default
    ``None`` is this, bit for bit), ``OverlayGain()`` (risk pre-folded
    into one raw gain table — the RawOverlay raw-value path), or
    ``ModelGain(...)`` (a trained predictor's jitted inference fills the
    tables).  Table/overlay reproduce today's decision streams
    bit-identically on every engine; the source only swaps the (S,)
    tables behind the fused lowering, so every engine above is
    unchanged.
    """
    from repro.serve.compile import (compile_service,
                                     compile_service_streaming,
                                     service_metrics)
    from repro.topology import validate_topology

    if engine not in ("scan", "chunked", "sharded"):
        raise ValueError(f"unknown engine {engine!r}; "
                         "expected scan | chunked | sharded")
    if engine == "sharded" and mesh is None:
        from repro.parallel.mesh import make_fleet_mesh
        mesh = make_fleet_mesh(axis=device_axis)
    validate_topology(topology, sim.T, sim.num_devices)

    if not materialize:
        if engine == "scan":
            raise ValueError(
                "materialize=False streams workload slabs per chunk; the "
                "scan engine needs the whole horizon — use "
                "engine='chunked' or 'sharded'")
        if on is not None:
            raise ValueError(
                "materialize=False generates arrivals on device; an "
                "arrival-matrix override needs materialize=True")
        from repro.core.fleet import (simulate_chunked_stream,
                                      simulate_sharded_stream)

        cs = compile_service_streaming(sim, pool, gain_source=gain_source)
        if engine == "chunked":
            series, _ = simulate_chunked_stream(
                cs.slab, sim.T, sim.num_devices, cs.tables, cs.params,
                cs.rule, chunk=chunk, slab=slab, block_n=block_n,
                algo=sim.algo, enforce_slot_capacity=True,
                topology=topology, topo_binned=topo_binned,
                pipelined=pipelined, source_aligned=cs.slab_aligned)
        else:
            series, _ = simulate_sharded_stream(
                cs.slab, sim.T, sim.num_devices, cs.tables, cs.params,
                cs.rule, mesh, device_axis=device_axis, slab=slab,
                algo=sim.algo, enforce_slot_capacity=True,
                topology=topology, source_cols=cs.slab_cols,
                pipelined=pipelined)
        return service_metrics(sim, series)

    cs = compile_service(sim, pool, on, gain_source=gain_source)
    if engine == "scan":
        series, _ = simulate(*cs.simulate_args(), cs.rule,
                             algo=sim.algo, ato_theta=sim.ato_theta,
                             enforce_slot_capacity=True, overlay=cs.overlay,
                             topology=topology)
    elif engine == "chunked":
        from repro.core.fleet import simulate_chunked
        series, _ = simulate_chunked(*cs.simulate_args(), cs.rule,
                                     chunk=chunk, block_n=block_n,
                                     algo=sim.algo, overlay=cs.overlay,
                                     enforce_slot_capacity=True,
                                     topology=topology,
                                     topo_binned=topo_binned)
    else:
        from repro.core.fleet import simulate_sharded
        series, _ = simulate_sharded(*cs.simulate_args(), cs.rule, mesh,
                                     device_axis=device_axis,
                                     algo=sim.algo, overlay=cs.overlay,
                                     enforce_slot_capacity=True,
                                     topology=topology)
    return service_metrics(sim, series)
