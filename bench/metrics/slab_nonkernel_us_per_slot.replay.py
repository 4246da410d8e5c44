"""Device time of the fused slab step outside the rollout kernel
(workload generation, value lowering, admission, accounting), per slot:
the slab step programs' device time less the kernel's."""

from bench import harness

SLAB_STEP = r"_pipelined_slab_step"


def read(ctx):
    trace, run = ctx["trace"], ctx["run"]
    step_s = trace.module_seconds(SLAB_STEP)
    if step_s <= 0 or not run.get("slots"):
        return None
    roof = harness.load_module(harness.BENCH / "roofline" /
                               "onalgo_rollout.py")
    return 1e6 * (step_s - trace.op_seconds(roof.TRACE_NAME)) / run["slots"]
