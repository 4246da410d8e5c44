#!/usr/bin/env python3
"""The readings a cell's limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> [--seeds 1 2 ...] \
        [--control-seeds 7 8 9]

For each of ``--seeds``, in one process: the cell's set-up, its timed
path walked over the span the check covers, and the check's numbers (the
program's sound readings).  For each of ``--control-seeds``, the control:
the plain reference computed in bfloat16, put in the program's place and
compared with the float32 reference by the same numbers.  A limit lies
above the largest sound reading and below the smallest control reading
(``bench/limits/<cell>.json``).  The benchmark's own runs never run this.
Prints one JSON line per seed, and the summary as the last line.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness  # noqa: E402


def control_numbers(runner_mod, cell, seed):
    """The bfloat16 reference in the program's place."""
    import jax.numpy as jnp
    drv = runner_mod.Runner(harness.RunContext(cell=cell, seed=seed))
    return drv.control(jnp.bfloat16)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.enable_compile_cache()
    devs = harness.tpu_devices(cell.chips)
    if devs is None:
        return 2
    mod = harness.load_module(cell.runner_path)
    sound, control = {}, {}
    for seed in args.seeds:
        t = time.perf_counter()
        drv = mod.Runner(harness.RunContext(cell=cell, seed=seed))
        drv.setup()
        drv.walk_check_span()
        drv.release()
        sound[seed] = drv.check()
        del drv
        gc.collect()
        print(json.dumps({"seed": seed, "program": sound[seed],
                          "s": time.perf_counter() - t}), flush=True)
    for seed in args.control_seeds:
        control[seed] = control_numbers(mod, cell, seed)
        print(json.dumps({"seed": seed, "control": control[seed]}),
              flush=True)
    names = sorted(next(iter({**sound, **control}.values())))
    summary = {n: {"lower": (max(s[n] for s in sound.values())
                             if sound else None),
                   "upper": (min(c[n] for c in control.values())
                             if control else None)}
               for n in names}
    print(json.dumps({"workload": cell.name, "seeds": args.seeds,
                      "control_seeds": args.control_seeds,
                      "readings": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
