#!/usr/bin/env python3
"""The gateway's knee, found once on the chip: the highest open-loop
wave rate it sustains.

    python3 bench/sweep_gateway.py --workload <gateway cell> --seed <n> \
        --rates 10 20 30 ... --seconds 8

One process sets the cell up once, then offers each rate in turn for
``--seconds`` (the gateway's state carries on; each rate's waves are
the next slots of the workload; the SLO is set out of reach).  Per rate
it prints one JSON line: the wave latency's p50 and p95 from due time,
fallbacks and shed chunks, and whether latency grew through the run (the
last quarter's median over the first quarter's).  The benchmark's own
runs never run this; the rate a cell offers is written into its traffic
file as a number.
"""

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--slo-ms", type=float, default=1e6)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.enable_compile_cache()
    if harness.tpu_devices(cell.chips) is None:
        return 2
    n_total = (int(cell.traffic["warm_waves"])
               + sum(int(r * args.seconds) + 1 for r in args.rates))
    cell.traffic = dict(cell.traffic, window_waves=n_total,
                        slo_ms=args.slo_ms)
    mod = harness.load_module(cell.runner_path)
    drv = mod.Runner(harness.RunContext(cell=cell, seed=args.seed))
    drv.setup()
    for rate in args.rates:
        drv.rate = rate
        r = drv._run(args.seconds)
        lat = r["lat"]
        q = max(len(lat) // 4, 1)
        print(json.dumps({
            "rate_hz": rate, "waves": r["attempted"],
            "failed": r["n_failed"], "p50_ms": r["p50"], "p95_ms": r["p95"],
            "reports_per_s": r["reports_per_s"],
            "growth": float(np.median(lat[-q:]) / np.median(lat[:q])),
            "fallback_waves": drv.gw_stats.fallback_waves,
            "shed_chunks": drv.gw_stats.shed_chunks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
