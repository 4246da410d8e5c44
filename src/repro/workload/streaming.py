"""Streaming (chunk-addressable) lowering of the service workload.

``generate_service_workload`` materializes the whole ``(T, N)`` horizon;
at fleet scale (N >> 10^4) those arrays — not the kernels — are the
memory ceiling.  This module exploits the counter-addressed v1 RNG
contract to make any slab ``[t0, t0 + L)`` of the workload a pure
O(L * N) function of counters, so the engines can generate workload
*per chunk, on device, inside the rollout loop* and peak memory becomes
independent of ``T * N``.

Two of the three processes carry state across slots:

  * the arrival chain is a two-state Markov recurrence — over {0, 1}
    transition *maps* it reduces exactly (booleans, no float
    re-association), so a one-off O(T/ROW_BLOCK * N) lowering pass scans
    the per-block maps and records the chain state *entering* every
    ROW_BLOCK-aligned block;
  * the channel rate holds between resample slots — the same pass
    carries the held value into each block.

With those per-block boundary states (``on_entry`` / ``rate_entry``,
64x smaller than the horizon and T-independent per slab), a slab is:
generate the covering blocks' uniforms (same keys/counters as the
materialized path), resume the chain / hold from the boundary state,
slice.  Every draw is bit-identical to the corresponding slice of
``generate_service_workload`` — slab boundaries are unobservable
(property-tested in tests/test_properties.py).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro import spans
from repro.workload import streams
from repro.workload.service import ServiceWorkload, arrival_chain_probs

def _static():
    return dataclasses.field(metadata={"static": True})


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class StreamingWorkload:
    """The service workload lowered to a chunk-addressable form.

    ``slab(t0, length)`` yields slots ``[t0, t0 + length)`` of the same
    realization ``generate_service_workload(seed, T, N, ...)`` would
    materialize, from O(length * N) device work and memory.  The
    dataclass is a pytree (static shape/config fields are metadata), so
    ``slab`` composes with jit/scan in the engines.
    """

    # per-block boundary states, (n_blocks, N)
    on_entry: jax.Array  # bool: arrival-chain state entering block b
    rate_entry: jax.Array  # int32: held channel rate entering block b
    # chain parameters (traced: sweeping loads reuses one compile)
    p_on: jax.Array
    p_stay: jax.Array
    p_change: jax.Array
    seed: jax.Array  # int32 scalar — the counter streams' root
    # static config
    T: int = _static()
    N: int = _static()
    pool_size: int = _static()
    num_rates: int = _static()

    @property
    def n_blocks(self) -> int:
        return self.on_entry.shape[0]

    def _finish_slab(self, u, on_in, rate_in, b0, nb: int, off,
                     length: int) -> ServiceWorkload:
        """Resume the chains from the block-b0 boundary states over the
        covering blocks' uniforms ``u``, then cut [off, off + length)."""
        RB = streams.ROW_BLOCK
        g_t = (jnp.int32(b0) * RB
               + jnp.arange(nb * RB, dtype=jnp.int32))  # global slots
        on = streams.markov_chain(u[0], on_in, self.p_on, self.p_stay)
        img = streams.levels_from_uniform(u[1], self.pool_size)
        change = (u[2] < self.p_change) | (g_t == 0)[:, None]
        rates = streams.hold_resample_from(
            change, streams.levels_from_uniform(u[3], self.num_rates),
            rate_in)
        cut = lambda x: jax.lax.dynamic_slice_in_dim(x, off, length, axis=0)
        return ServiceWorkload(on=cut(on), img=cut(img), rates=cut(rates))

    def slab(self, t0, length: int, *, aligned: bool = False
             ) -> ServiceWorkload:
        """Slots [t0, t0 + length) of the realized workload.

        ``t0`` may be traced (the engines sweep it inside one compiled
        slab step); ``length`` is static.  Requires t0 + length <= T.

        ``aligned=True`` promises ``t0 % ROW_BLOCK == 0`` (the caller's
        burden — t0 may be traced, so it cannot be checked here): the
        slab then starts exactly on a block boundary and one fewer
        covering block is generated (at length == ROW_BLOCK that halves
        the uniforms drawn per slab).  Counter addressing makes the
        result bit-identical to the unaligned path.
        """
        RB = streams.ROW_BLOCK
        if aligned:
            nb = (length - 1) // RB + 1  # t0 starts a block: no lead-in
            b0 = t0 // RB
            off = 0
        else:
            nb = (length - 1) // RB + 2  # covers any offset within a block
            b0 = t0 // RB
            off = t0 - b0 * RB
        with jax.named_scope(spans.WORKLOAD_GEN):
            u = streams.uniform_block_range(
                self.seed, streams.STREAM_SERVICE, b0, nb, self.N, 4)
            on_in = jax.lax.dynamic_index_in_dim(self.on_entry, b0,
                                                 keepdims=False)
            rate_in = jax.lax.dynamic_index_in_dim(self.rate_entry, b0,
                                                   keepdims=False)
            return self._finish_slab(u, on_in, rate_in, b0, nb, off,
                                     length)

    def slab_cols(self, t0, length: int, n0, n_cols: int, *,
                  aligned: bool = False) -> ServiceWorkload:
        """Device columns [n0, n0 + n_cols) of ``slab(t0, length)``.

        Bit-identical to slicing the full-width slab — the counter-offset
        draw primitive addresses each device by its ABSOLUTE column — but
        from O(length * n_cols) work and memory, so a fleet shard can
        generate exactly its own devices' workload
        (``fleet.simulate_sharded_stream(source_cols=...)``).  ``t0`` and
        ``n0`` may be traced (e.g. an ``axis_index`` offset inside
        shard_map); ``length`` / ``n_cols`` are static.  ``aligned``:
        see :meth:`slab`.
        """
        RB = streams.ROW_BLOCK
        if aligned:
            nb = (length - 1) // RB + 1
            b0 = t0 // RB
            off = 0
        else:
            nb = (length - 1) // RB + 2
            b0 = t0 // RB
            off = t0 - b0 * RB
        cols = lambda x: jax.lax.dynamic_slice_in_dim(x, n0, n_cols,
                                                      axis=-1)
        with jax.named_scope(spans.WORKLOAD_GEN):
            u = streams.uniform_block_range(
                self.seed, streams.STREAM_SERVICE, b0, nb, self.N, 4,
                n0=n0, n_cols=n_cols)
            on_in = cols(jax.lax.dynamic_index_in_dim(self.on_entry, b0,
                                                      keepdims=False))
            rate_in = cols(jax.lax.dynamic_index_in_dim(
                self.rate_entry, b0, keepdims=False))
            return self._finish_slab(u, on_in, rate_in, b0, nb, off,
                                     length)


@partial(jax.jit,
         static_argnames=("T", "N", "pool_size", "num_rates", "burst_len"))
def lower_service_workload(seed, T: int, N: int, pool_size: int,
                           num_rates: int,
                           burst_len: Tuple[int, int] = (5, 10),
                           mean_gap=8.0,
                           channel_stay=0.9) -> StreamingWorkload:
    """Lower the ``(seed, T, N)`` service workload to streaming form.

    One jitted scan over the horizon's ROW_BLOCK-aligned blocks computes
    the arrival-chain and held-rate boundary states; peak memory is
    O(ROW_BLOCK * N) transient + O(T/ROW_BLOCK * N) boundaries — never
    the (T, N) horizon.  Both recurrences are exact (boolean chain
    composition, integer holds), so slabs reproduce the materialized
    draws bit for bit.
    """
    RB = streams.ROW_BLOCK
    mean_gap = jnp.float32(mean_gap)
    p_on, p_stay, p_init = arrival_chain_probs(burst_len, mean_gap)
    p_on, p_stay = jnp.float32(p_on), jnp.float32(p_stay)
    p_change = 1.0 - jnp.float32(channel_stay)
    u0 = streams.uniform_vector(seed, streams.STREAM_ARRIVAL_INIT, N)
    s0 = u0 < p_init
    n_blocks = -(-T // RB)

    def block(carry, b):
        on_in, rate_in = carry
        u = streams.uniform_block_range(seed, streams.STREAM_SERVICE, b, 1,
                                        N, 4)  # (4, RB, N)
        on_blk = streams.markov_chain(u[0], on_in, p_on, p_stay)
        g_t = jnp.int32(b) * RB + jnp.arange(RB, dtype=jnp.int32)
        change = (u[2] < p_change) | (g_t == 0)[:, None]
        rates_blk = streams.hold_resample_from(
            change, streams.levels_from_uniform(u[3], num_rates), rate_in)
        return (on_blk[-1], rates_blk[-1]), (on_in, rate_in)

    r0 = jnp.zeros((N,), jnp.int32)  # never read: slot 0 forces a redraw
    _, (on_entry, rate_entry) = jax.lax.scan(
        block, (s0, r0), jnp.arange(n_blocks, dtype=jnp.uint32))
    return StreamingWorkload(
        on_entry=on_entry, rate_entry=rate_entry, p_on=p_on, p_stay=p_stay,
        p_change=p_change, seed=jnp.asarray(seed, jnp.int32),
        T=T, N=N, pool_size=pool_size, num_rates=num_rates)
