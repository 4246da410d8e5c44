"""The gateway's host path per wave (the wave's pad and copy to the
device, dispatch and resolve in ``LiveGateway``, the copy back, and the
wait in its pipeline): the mean latency of the waves served in the
traced window, from due time to decisions on the host, less the mean
device time of their ticks."""

import numpy as np

from bench import harness


def read(ctx):
    run = ctx["run"]
    lat = np.asarray(run["lat_ms"])[np.asarray(run["served"], bool)]
    tick = harness.load_module(harness.BENCH / "metrics"
                               / "tick_device_ms.gw.py")
    tick_ms = tick.read(ctx)
    if tick_ms is None or lat.size == 0:
        return None
    return float(np.mean(lat)) - tick_ms
