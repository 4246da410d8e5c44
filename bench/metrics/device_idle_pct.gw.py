"""Share of the traced window in which no operation ran on the chip,
while the gateway serves its open loop."""


def read(ctx):
    trace = ctx["trace"]
    if trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
