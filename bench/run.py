#!/usr/bin/env python3
"""One run of one benchmark cell, on the chips it asks for.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; ``bench/harness.py`` finds their files by name.  A run
makes its inputs from ``--seed``, warms the cell's own programs (set-up,
reported as ``setup_s``: from process start to the first measured
instant), then with ``--trace 0`` measures for ``--seconds`` with the
profiler off and reports the cell's end-to-end metrics, or with
``--trace 1`` traces a short window of its own and reports the cell's
per-layer metrics.  Either way it then frees the program's state, checks
what the timed path produced against the plain reference
(``bench/reference``), prints each compared number beside its limit as
the last lines of standard error, and prints one JSON result as the last
line of standard output.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))  # the system under test

from bench import harness, tracing  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def per_layer(cell, run, trace, peaks):
    """Each per-layer metric's reader, found by the metric's name; a
    reader that finds nothing to read returns None and is left out."""
    ctx = {"cell": cell, "run": run, "trace": trace, "peaks": peaks}
    out = {}
    for m in cell.per_layer:
        reader = harness.load_module(harness.BENCH / "metrics"
                                     / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is None:
            harness.log(f"per-layer {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None):
    args = parse(argv)
    cell = harness.load_cell(args.workload)
    cache = harness.enable_compile_cache()
    devs = harness.tpu_devices(cell.chips)
    if devs is None:
        return 2
    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs)}
    harness.log(f"device: platform {d.platform}, device_kind "
                f"{d.device_kind}, count {len(devs)}; compile cache {cache}")
    peaks = harness.peaks(d.device_kind)
    count = harness.CompileCount()
    runner = harness.load_module(cell.runner_path).Runner(
        harness.RunContext(cell=cell, seed=args.seed))
    runner.setup()
    harness.log(f"set-up: {count}")
    count.reset()
    setup_s = time.perf_counter() - T_START
    if args.trace:
        with tempfile.TemporaryDirectory() as tdir:  # under $TMPDIR
            res, trace = tracing.capture(
                lambda: runner.traced(args.seconds), tdir, devs)
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
    else:
        res = runner.measure(args.seconds)
    harness.log(f"compiles inside the window: {count.compiles} "
                f"({count})")
    device["memory_peak_bytes"] = harness.peak_bytes(devs)
    metrics = {}
    if args.trace:
        metrics = per_layer(cell, res, trace, peaks)
    else:
        for name, (value, unit) in res["metrics"].items():
            metrics[name] = {"value": float(value), "unit": unit}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        want = {m["name"]: m["unit"] for m in cell.end_to_end}
        if {k: v["unit"] for k, v in metrics.items()} != want:
            raise harness.CellError(f"the runner reported {sorted(metrics)}"
                                    f", BENCHMARK.json lists {sorted(want)}")
    bad = {k: v["value"] for k, v in metrics.items()
           if not math.isfinite(v["value"])}
    if bad:
        # a latency rank held by a failed wave, or a rate over no time:
        # not a reading, so the run prints no result
        harness.log(f"metrics that are not finite numbers: {bad}")
        return 1
    runner.release()
    t = time.perf_counter()
    numbers = runner.check()
    harness.log(f"check: reference ran {time.perf_counter() - t:.3f} s")
    check = {}
    for name, value in numbers.items():
        if name not in cell.limits:
            raise harness.CellError(f"no limit for {name!r} in "
                                    f"bench/limits/{cell.name}.json")
        check[name] = {"value": value, "limit": cell.limits[name]}
    correct = all(c["value"] <= c["limit"] for c in check.values())
    result = {"correct": correct, "attempted": res["attempted"],
              "failed": res["failed"], "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = trace.breakdown()
    result["check"] = check
    for name, c in check.items():
        harness.log(f"check {name}: {c['value']!r} (limit {c['limit']!r})"
                    f"{'' if c['value'] <= c['limit'] else '  FAILS'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
