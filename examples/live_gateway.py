"""Live serving gateway: OnAlgo deciding online, wave by wave.

A closed-loop load generator plays the fleet — each slot it submits the
devices whose arrival fired, with the raw (o, h, w) each observed — and
the async gateway micro-batches the reports, ticks Algorithm 1 once per
slot, applies cloudlet admission, and streams the decisions back under a
latency SLO.  At the end, the decision stream is checked bit for bit
against the batch ``fleet.simulate`` replay of the same counters.

With ``--pipeline``, the same horizon is also served through the
depth-bounded wave pipeline (``max_in_flight=2``: wave t+1 dispatches
while wave t's decisions are in flight, after a bucket-ladder
``warmup()``) and its decision stream is checked against both the
sequential run and the batch replay — overlap moves the wall clock,
never the decisions.

    PYTHONPATH=src python examples/live_gateway.py [--pipeline]
"""

import sys
import time

import numpy as np

from repro.core import fleet
from repro.serve.compile import compile_service, compile_service_streaming
from repro.serve.gateway import GatewayCore, run_closed_loop, \
    run_pipelined_loop
from repro.serve.simulator import SimConfig, synthetic_pool
from repro.workload.loadgen import ServiceLoadGen

N, T = 256, 384
PIPE_DEPTH = 2


def _masks(replies, lg):
    off = np.zeros((T, N), bool)
    adm = np.zeros_like(off)
    for t, r in enumerate(replies):
        wv = lg.wave(t)
        off[t, wv.idx] = r.offload
        adm[t, wv.idx] = r.admitted
    return off, adm


def main(pipeline: bool = False):
    pool = synthetic_pool()
    sim = SimConfig(num_devices=N, T=T, algo="onalgo", seed=11)
    ss = compile_service_streaming(sim, pool)

    core = GatewayCore.for_service(ss)
    lg = ServiceLoadGen(ss)
    print(f"== live gateway: N={N} devices, {T} slots, closed loop ==")
    t0 = time.perf_counter()
    replies, stats = run_closed_loop(core, lg, 0, T, slo_ms=30_000.0,
                                     max_queue=8)
    wall_closed = time.perf_counter() - t0
    s = stats.summary()
    offloads = sum(int(r.offload.sum()) for r in replies)
    admits = sum(int(r.admitted.sum()) for r in replies)
    print(f"  waves served        : {s['waves']} "
          f"({s['reports']} reports, {core.stats.compiles} compiles)")
    print(f"  offloads / admits   : {offloads} / {admits}")
    print(f"  wave latency        : p50 {s['p50_ms']:.2f} ms, "
          f"p99 {s['p99_ms']:.2f} ms")
    print(f"  degradation         : {s['fallback_waves']} fallback waves, "
          f"{s['shed_chunks']} shed chunks, "
          f"queue peak {s['max_queue_seen']}")
    print(f"  final mu            : {float(core.mu):.4f}")

    # the online decision stream == the batch replay of the same counters
    cs = compile_service(sim, pool)
    series, _ = fleet.simulate(cs.trace, cs.tables, cs.params, cs.rule,
                               algo="onalgo", overlay=cs.overlay,
                               enforce_slot_capacity=True,
                               collect_decisions=True)
    off, adm = _masks(replies, lg)
    ok = (np.array_equal(off, np.asarray(series["offload_mask"]))
          and np.array_equal(adm, np.asarray(series["admit_mask"])))
    print(f"  == batch replay     : "
          f"{'bit-identical' if ok else 'MISMATCH'} ==")
    if not ok:
        raise SystemExit(1)

    if not pipeline:
        return

    print(f"== pipelined serve loop: max_in_flight={PIPE_DEPTH}, "
          f"warmed bucket ladder ==")
    core_p = GatewayCore.for_service(ss)
    core_p.warmup()  # compiles off the serve path
    lg_p = ServiceLoadGen(ss, prefetch=True)
    t0 = time.perf_counter()
    replies_p, stats_p = run_pipelined_loop(
        core_p, lg_p, 0, T, max_in_flight=PIPE_DEPTH, slo_ms=30_000.0)
    wall_pipe = time.perf_counter() - t0
    sp = stats_p.summary()
    print(f"  waves served        : {sp['waves']} "
          f"({sp['overlapped_waves']} overlapped, pipe depth peak "
          f"{sp['max_in_flight_seen']})")
    print(f"  wall clock          : {wall_pipe * 1e3:.0f} ms pipelined "
          f"vs {wall_closed * 1e3:.0f} ms closed loop")
    off_p, adm_p = _masks(replies_p, lg_p)
    ok_p = (np.array_equal(off_p, off) and np.array_equal(adm_p, adm)
            and sp["fallback_waves"] == 0)
    print(f"  == vs sequential + batch replay: "
          f"{'bit-identical' if ok_p else 'MISMATCH'} ==")
    if not ok_p:
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(pipeline="--pipeline" in sys.argv[1:])
