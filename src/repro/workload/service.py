"""Counter-based generation of the service tier's workload processes.

A :class:`ServiceWorkload` bundles the three random processes the paper's
end-to-end experiments (Figs. 5-8) drive the service with:

  * ``on``    — bursty ON/OFF arrivals (Markov chain matched to the legacy
                renewal process: mean burst length (lo+hi)/2, mean gap
                1 + mean_gap slots);
  * ``img``   — the per-slot image stream (iid indices into the pool);
  * ``rates`` — the Markov channel (rate holds w.p. ``stay``, else redraws).

Everything is generated on device from counter-addressed streams
(:mod:`repro.workload.streams`): slot (t, n) of each process is a pure
function of ``(seed, stream_id, t, n)``, so any engine — scan, chunked,
sharded, or the per-chunk streaming lowering — can materialize exactly
the same workload without replaying a host RNG's draw order.  This is
RNG contract v1 (``rng_version=1``); the retired v0 host loop survives
only as the pinned golden fixture (see :mod:`repro.workload.streams`).

At fleet scale, :mod:`repro.workload.streaming` lowers the same
processes to a chunk-addressable :class:`StreamingWorkload` so engines
never hold the (T, N) horizon at once.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.workload import streams
from repro.workload.streams import RNG_COUNTER, RNG_LEGACY_HOST


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ServiceWorkload:
    """Realized service workload: (T, N) arrival mask, image ids, rates."""

    on: jax.Array  # (T, N) bool arrivals
    img: jax.Array  # (T, N) int32 image-pool indices
    rates: jax.Array  # (T, N) int32 channel-rate indices


def arrival_chain_probs(burst_len: Tuple[int, int], mean_gap):
    """(p_on, p_stay, p_init) of the Markov ON/OFF chain that matches the
    legacy renewal arrivals in the mean: bursts average (lo + hi)/2 slots,
    gaps average 1 + mean_gap slots; p_init is the stationary ON share.

    ``mean_gap`` may be a float or a traced jax scalar (the service
    generator traces it so sweeping loads doesn't recompile)."""
    mean_on = max((burst_len[0] + burst_len[1]) / 2.0, 1.0)
    mean_off = 1.0 + mean_gap
    p_stay = 1.0 - 1.0 / mean_on
    p_on = 1.0 / mean_off
    p_init = mean_on / (mean_on + mean_off)
    return p_on, p_stay, p_init


@partial(jax.jit,
         static_argnames=("T", "N", "pool_size", "num_rates", "burst_len"))
def generate_service_workload(seed, T: int, N: int, pool_size: int,
                              num_rates: int,
                              burst_len: Tuple[int, int] = (5, 10),
                              mean_gap=8.0,
                              channel_stay=0.9) -> ServiceWorkload:
    """Materialize the v1 service workload for ``(seed, T, N)`` on device.

    One uniform block feeds all four per-slot channels (arrival chain,
    image draw, channel flip, candidate rate) — a single threefry sweep
    per workload, each value still addressed by (seed, sid, c, t, n).
    ``mean_gap`` / ``channel_stay`` are traced, so sweeping loads (e.g.
    the fig6 bursts/min grid) shares one compiled program.
    """
    mean_gap = jnp.float32(mean_gap)
    p_on, p_stay, p_init = arrival_chain_probs(burst_len, mean_gap)
    u = streams.uniform_block(seed, streams.STREAM_SERVICE, T, N, 4)
    u0 = streams.uniform_vector(seed, streams.STREAM_ARRIVAL_INIT, N)
    on = streams.markov_chain(u[0], u0 < p_init, jnp.float32(p_on),
                              jnp.float32(p_stay))
    img = streams.levels_from_uniform(u[1], pool_size)
    rates = streams.hold_resample(
        u[2] < 1.0 - jnp.float32(channel_stay),
        streams.levels_from_uniform(u[3], num_rates))
    return ServiceWorkload(on=on, img=img, rates=rates)


def validate_rng_version(rng_version: int) -> int:
    if rng_version == RNG_LEGACY_HOST:
        raise ValueError(
            "rng_version=0 (legacy host draw order) is retired: the pinned "
            "golden fixture (tests/golden/service_legacy_fig5.json) and its "
            "frozen sampler (tests/legacy_workload.py) are its only "
            "residue — use the counter-based v1 contract")
    if rng_version != RNG_COUNTER:
        raise ValueError(
            f"unknown rng_version {rng_version!r}; the only live contract "
            f"is {RNG_COUNTER} (counter-based streams)")
    return rng_version
