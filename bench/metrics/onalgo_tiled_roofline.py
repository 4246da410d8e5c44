"""Share of the rollout kernel's roofline: the least time the chip could
take for the slots traced (the larger of flops over peak FLOP/s and
bytes over peak HBM bandwidth, from ``bench/roofline/onalgo_rollout.py``)
over the device time of the kernel's operations in the trace."""

from bench import harness


def read(ctx):
    trace, run, peaks = ctx["trace"], ctx["run"], ctx["peaks"]
    roof = harness.load_module(harness.BENCH / "roofline" /
                               "onalgo_rollout.py")
    kernel_s = trace.op_seconds(roof.TRACE_NAME)
    if kernel_s <= 0:
        return None
    cfg = ctx["cell"].config
    c = roof.counts(int(cfg["num_devices"]), int(run["states"]),
                    int(cfg["horizon"]), int(run["slots"]),
                    int(cfg["engine"]["slab"]), float(peaks["vmem_bytes"]))
    t_flops = c["flops"] / peaks["flops_per_s"]
    t_bytes = c["bytes"] / peaks["hbm_bytes_per_s"]
    harness.log(f"roofline: {c['flops']:.6g} flops, {c['bytes']:.6g} bytes "
                f"over {run['slots']} slots; bound by "
                f"{'memory' if t_bytes >= t_flops else 'compute'}; "
                f"kernel {kernel_s:.6f} s")
    return 100.0 * max(t_flops, t_bytes) / kernel_s
