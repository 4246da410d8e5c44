"""Device time of the gateway's jitted tick (scatter, quantize,
``onalgo.step``, admission, gathers), per wave served in the trace."""

TICK = r"jit_tick"


def read(ctx):
    trace = ctx["trace"]
    n = trace.module_count(TICK)
    if n <= 0:
        return None
    return 1e3 * trace.module_seconds(TICK) / n
