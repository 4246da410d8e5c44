"""Counter-based random streams: the workload layer's RNG primitives.

Every random value a workload consumes is addressed, not drawn: the
value feeding process channel ``c`` at slot ``t`` for device ``n`` of
stream ``sid`` is a pure function of ``(seed, sid, c, t, n)``.
Concretely each stream owns a threefry key ``fold_in(PRNGKey(seed),
sid)``, each *block* of ``ROW_BLOCK`` consecutive slots owns the key
``fold_in(stream_key, t // ROW_BLOCK)``, and ``(t % ROW_BLOCK, c, n)``
indexes the block's counters, so

  * draws are reproducible regardless of host draw order — there is no
    hidden RNG cursor to keep in sync between code paths;
  * generation is fully jittable/vmappable and runs on device, one
    fused threefry sweep per stream (all channels and all slots of a
    block share one key — T/ROW_BLOCK folds, not T);
  * for a fixed fleet width N and channel count, extending the horizon
    T extends the stream without perturbing the prefix (block keys and
    in-block counters don't move; ROW_BLOCK is a contract constant).

This is the ``rng_version >= 1`` contract (``RNG_COUNTER``).  The legacy
contract ``rng_version == 0`` (``RNG_LEGACY_HOST``) was the seed repo's
stateful host-order numpy sampling; it is retired — the pinned golden
fixture (``tests/golden/service_legacy_fig5.json``) and its frozen
test-side sampler (``tests/legacy_workload.py``) are its only residue.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# --- RNG contract versions -------------------------------------------------
RNG_LEGACY_HOST = 0  # v0: host-order numpy draws (golden fixture only)
RNG_COUNTER = 1  # v1: counter-based streams (this module)

# --- stream ids (one per independent random process) -----------------------
STREAM_SERVICE = 1  # the service workload block (arrival/image/channel)
STREAM_ARRIVAL_INIT = 2  # initial ON/OFF state uniforms
STREAM_SCENARIO = 3  # scenario-engine arrival processes
STREAM_TOPOLOGY = 4  # cloudlet-association processes (mobility walks)

# Slots per block key (a v1 contract constant: changing it changes every
# stream's realized values, so it would need a new rng_version).
ROW_BLOCK = 64


def stream_key(seed, sid: int):
    """The threefry key owning stream ``sid`` of workload ``seed``."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), sid)


def _block_keys(seed, sid: int, n_blocks: int, b0=0):
    """(n_blocks,) keys for blocks [b0, b0 + n_blocks) — block b is
    ``fold_in(stream_key, b)``, independent of the horizon.  ``b0`` may
    be a traced scalar (the streaming lowering addresses blocks by
    offset)."""
    fold = jax.vmap(jax.random.fold_in, in_axes=(None, 0))
    blocks = jnp.uint32(b0) + jnp.arange(n_blocks, dtype=jnp.uint32)
    return fold(stream_key(seed, sid), blocks)


def _unit(bits):
    """uint32 words -> U[0, 1) float32: the top 23 bits become a float32
    mantissa with exponent 0 (a value in [1, 2)), minus 1."""
    f = jax.lax.bitcast_convert_type(
        (bits >> 9) | jnp.uint32(0x3F800000), jnp.float32) - 1.0
    return jnp.maximum(f, 0.0)


def _uniform_pairs(key, x0, x1):
    """U[0, 1) draws of ``key`` at explicit threefry counter pairs.

    This function IS the repo's RNG contract: every stream value is drawn
    here, from counters the caller addresses, so the bits belong to the
    repo rather than to a JAX default (``jax.random.uniform``'s counter
    layout depends on the ``jax_threefry_partitionable`` flag).  One
    Threefry-2x32 hash of the pair (x0, x1) yields the draws at both
    counters.  Every stream pairs the first half of its flattened counter
    grid with the second — the layout of ``jax.random.uniform`` with the
    flag off, under which the streams were defined.  A block grid's
    outermost axis is the slot-in-block row, so row r pairs with row
    r + ROW_BLOCK / 2 whatever the column range: a shard draws its own
    device columns bit-identical to slicing the full-width draw
    (tests/test_workload.py pins both facts).
    """
    from jax.extend.random import threefry2x32_p
    y0, y1 = threefry2x32_p.bind(key[0], key[1], x0, x1)
    return _unit(y0), _unit(y1)


def uniform_vector(seed, sid: int, n: int) -> jax.Array:
    """(n,) U[0, 1) draws of stream ``sid`` addressed by position (e.g.
    per-device initial states); an odd n pairs its last counter with 0."""
    half = (n + 1) // 2
    x0 = jnp.arange(half, dtype=jnp.uint32)
    x1 = jnp.where(x0 + half < n, x0 + half, 0).astype(jnp.uint32)
    u0, u1 = _uniform_pairs(stream_key(seed, sid), x0, x1)
    return jnp.concatenate([u0, u1])[:n]


def uniform_block_range(seed, sid: int, b0, n_blocks: int, N: int,
                        channels: int, n0=None,
                        n_cols: int = None) -> jax.Array:
    """(channels, n_blocks * ROW_BLOCK, n_cols or N) U[0, 1) slab covering
    blocks [b0, b0 + n_blocks) of stream ``sid``.

    Row r of the slab is global slot ``(b0 + r // ROW_BLOCK) * ROW_BLOCK
    + r % ROW_BLOCK``; values are identical to the corresponding rows of
    :func:`uniform_block` over any horizon (block keys and in-block
    counters are offset-independent) — this is what makes per-chunk
    on-device generation bit-equal to a whole-horizon materialization.
    ``b0`` may be traced; ``n_blocks`` must be static.

    Within a block, the value at (row r, channel c, device n) has counter
    ``(r * channels + c) * N + n`` under the block's key.  With ``n0`` /
    ``n_cols`` set, only device columns [n0, n0 + n_cols) are generated
    — addressed by their *absolute* counters, so the result is
    bit-identical to slicing the full-width draw, from O(rows * n_cols)
    work (the shard-local generation primitive of
    ``simulate_sharded_stream``).  ``n0`` may be traced (e.g. an
    ``axis_index`` offset inside ``shard_map``); ``n_cols`` is static.
    """
    if (n0 is None) != (n_cols is None):
        raise ValueError("n0 and n_cols must be passed together")
    if n_cols is None:
        n0, n_cols = 0, N
    keys = _block_keys(seed, sid, n_blocks, b0)
    half = ROW_BLOCK // 2
    r = jnp.arange(half, dtype=jnp.uint32)[:, None, None]
    c = jnp.arange(channels, dtype=jnp.uint32)[None, :, None]
    dn = jnp.arange(n_cols, dtype=jnp.uint32)[None, None, :]
    x0 = (r * channels + c) * jnp.uint32(N) + jnp.uint32(n0) + dn
    x1 = x0 + jnp.uint32(half * channels * N)  # row r + ROW_BLOCK / 2
    vals = jax.vmap(lambda k: jnp.concatenate(
        _uniform_pairs(k, x0, x1)))(keys)  # (nb, ROW_BLOCK, C, n_cols)
    return vals.reshape(n_blocks * ROW_BLOCK, channels, n_cols).transpose(
        1, 0, 2)


def uniform_block(seed, sid: int, T: int, N: int, channels: int
                  ) -> jax.Array:
    """(channels, T, N) U[0, 1) grid addressed by (seed, sid, c, t, n).

    All channels of a slot come from one block draw (counter
    ((t % ROW_BLOCK) * channels + c) * N + n under the block's key), so
    a stream that needs several independent per-(t, n) uniforms — e.g.
    arrivals + image + channel flips — pays a single threefry sweep
    instead of one per process.
    """
    n_blocks = -(-T // ROW_BLOCK)
    return uniform_block_range(seed, sid, 0, n_blocks, N, channels)[:, :T]


def uniforms(seed, sid: int, T: int, N: int) -> jax.Array:
    """(T, N) U[0, 1) grid addressed by (seed, sid, t, n)."""
    return uniform_block(seed, sid, T, N, 1)[0]


def levels_from_uniform(u: jax.Array, num_levels: int) -> jax.Array:
    """Map U[0, 1) draws to uniform int32 levels [0, num_levels).

    floor(u * L) with a defensive clamp at L - 1 (float32 rounding);
    the ~L/2^24 non-uniformity is far below workload-model resolution.
    """
    idx = jnp.floor(u * num_levels).astype(jnp.int32)
    return jnp.minimum(idx, num_levels - 1)


def markov_chain(u: jax.Array, s0: jax.Array, p_on, p_stay) -> jax.Array:
    """(T, N) bool two-state Markov chain from per-slot uniforms ``u``.

    OFF -> ON w.p. ``p_on``; ON stays ON w.p. ``p_stay``; ``s0`` (N,)
    bool is the state entering slot 0's transition.  A sequential scan
    over the T slots (an associative scan over per-slot transition maps
    gives the same booleans but compiles for over a minute at
    N = 2^20 on the TPU).
    """
    def slot(s, u_t):
        s = jnp.where(s, u_t < p_stay, u_t < p_on)
        return s, s

    return jax.lax.scan(slot, jnp.asarray(s0, bool), u)[1]


def hold_resample_from(change: jax.Array, candidates: jax.Array,
                       entry: jax.Array) -> jax.Array:
    """(T, N) piecewise-constant process resuming from ``entry`` (N,).

    At each ``change`` slot the value jumps to that slot's ``candidates``
    entry, else it holds; before the first change it holds ``entry`` —
    the value carried in from the slots preceding this slab.  Stateless
    formulation: the value at t is the candidate at the most recent
    change-slot <= t (a running cummax over change-slot indices), or
    ``entry`` when no change has happened yet.
    """
    T = change.shape[0]
    t_idx = jnp.arange(T, dtype=jnp.int32)[:, None]
    last = jax.lax.cummax(jnp.where(change, t_idx, -1), axis=0)  # (T, N)
    picked = jnp.take_along_axis(candidates, jnp.maximum(last, 0), axis=0)
    return jnp.where(last >= 0, picked, entry[None, :])


def hold_resample(change: jax.Array, candidates: jax.Array) -> jax.Array:
    """(T, N) piecewise-constant process: at each ``change`` slot the
    value jumps to that slot's ``candidates`` entry, else it holds.
    Slot 0 always draws fresh.
    """
    change = change.at[0].set(True)  # initial draw
    return hold_resample_from(change, candidates, candidates[0])
