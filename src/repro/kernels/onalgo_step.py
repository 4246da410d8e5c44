"""Pallas TPU kernels for the paper's hot loop: fused OnAlgo policy + dual
subgradient reductions over the device fleet.

At production scale (10^5-10^7 devices x M quantized states) the per-slot
work is: threshold policy y = 1{lam o + mu h < w} over the (N, M) table,
then two rho-weighted reductions (per-device power slack, global cloudlet
load).  The jnp path makes ~5 HBM passes over (N, M); these kernels tile
devices into VMEM blocks (block_n x M) and produce the policy, the power
slack, and the load partial sums in ONE pass.

Layout rules every kernel here follows (the TPU compiler refuses anything
else): the last two dims of every block are multiples of (8, 128) or the
whole array's dims; M is padded to a lane multiple (128) with w = 0
columns (zero-gain states never offload, so padding is inert); scalar
operands (the step rule, the capacity, the resume offset t0) live in SMEM;
per-slot series are (T, rows, lanes) arrays whose trailing dims are whole,
so one slot's entry is a leading-dim index.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)
_HBM = pl.BlockSpec(memory_space=pl.ANY)


def _onalgo_kernel(mu_ref, lam_ref, rho_ref, o_ref, h_ref, w_ref, b_ref,
                   gpow_ref, load_ref):
    lam = lam_ref[...].astype(jnp.float32)  # (bn, 1)
    mu = mu_ref[0]
    rho = rho_ref[...].astype(jnp.float32)  # (bn, M)
    o = o_ref[...].astype(jnp.float32)
    h = h_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)

    price = lam * o + mu * h
    y = jnp.where((price < w) & (w > 0), 1.0, 0.0)
    ry = rho * y
    gpow_ref[...] = ((o * ry).sum(axis=-1, keepdims=True)
                     - b_ref[...].astype(jnp.float32))
    load_ref[0] = jnp.sum(h * ry, keepdims=True)


def onalgo_duals_pallas(lam, mu, rho, o_tab, h_tab, w_tab, B, *,
                        block_n=256, interpret=True):
    """Matches kernels/ref.onalgo_duals_ref. Returns (g_pow (N,), load ())."""
    N, M = rho.shape
    o = jnp.broadcast_to(o_tab, (N, M)).astype(jnp.float32)
    h = jnp.broadcast_to(h_tab, (N, M)).astype(jnp.float32)
    w = jnp.broadcast_to(w_tab, (N, M)).astype(jnp.float32)

    # pad M to lane multiple with inert (w=0) states; pad N to block multiple
    M_pad = -M % 128
    N_pad = -N % block_n
    if M_pad:
        z = lambda x: jnp.pad(x, ((0, 0), (0, M_pad)))
        rho, o, h, w = z(rho), z(o), z(h), z(w)
    if N_pad:
        rho = jnp.pad(rho, ((0, N_pad), (0, 0)))
        o = jnp.pad(o, ((0, N_pad), (0, 0)))
        h = jnp.pad(h, ((0, N_pad), (0, 0)))
        w = jnp.pad(w, ((0, N_pad), (0, 0)))
    lam_p = jnp.pad(lam.astype(jnp.float32), (0, N_pad))[:, None]
    B_p = jnp.pad(jnp.broadcast_to(B, (N,)).astype(jnp.float32),
                  (0, N_pad))[:, None]
    Np, Mp = rho.shape
    n_tiles = Np // block_n
    mu_arr = jnp.full((1,), mu, jnp.float32)
    tile = pl.BlockSpec((block_n, Mp), lambda i: (i, 0))
    col = pl.BlockSpec((block_n, 1), lambda i: (i, 0))

    gpow, load = pl.pallas_call(
        _onalgo_kernel,
        name="onalgo_duals",
        grid=(n_tiles,),
        in_specs=[_SMEM, col, tile, tile, tile, tile, col],
        out_specs=[col, pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((Np, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_tiles, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(mu_arr, lam_p, rho, o, h, w, B_p)
    return gpow[:N, 0], load.sum()


# ---------------------------------------------------------------------------
# Time-chunked whole-simulation kernels.
#
# The single-slot kernel above amortizes the ~5 HBM passes of one dual
# update, but a T-slot simulation still pays one kernel launch + one
# (N, M) table round-trip per slot.  The rollout kernels run the ENTIRE
# horizon in one pallas_call, chunk slots per grid step of the trace.
#
# Two variants, chosen from N and M by ``rollout_block_n``:
#
#   whole-fleet (``onalgo_chunked_pallas``): grid (K chunks,); the whole
#     fleet's tables and state (lam, mu, visit counts) stay resident in
#     VMEM for the whole rollout (constant-index blocks, flushed to HBM
#     once), and grid step k loops over its C slots.  Bounded by VMEM:
#     ~a dozen (N, M) fp32 buffers.
#   device-tiled (``onalgo_tiled_pallas``): grid (K, C, n_tiles); one
#     (block_n, M) tile per grid step, so VMEM use is O(block_n * M)
#     whatever the fleet size (see its section below).
#
# Layout: per-slot streams (state indices, the service overlay's raw slot
# values, time-varying cloudlet ids, the offload decisions) stay in their
# natural lane-dense (T, N_pad) layout — no transpose in XLA, no padded
# lanes in HBM — and ride (C, N) blocks; the kernel transposes each block
# once so a slot's values are an (N, 1) column against the (N, M) tables.
# Padded devices sit in no state (j = -1) with B = 0: no visit counts, so
# they never price, offload or load, and their duals stay 0.  States are
# padded to the lane multiple with w = 0 columns.  Tables shared by the
# fleet ((M,) — e.g. h and w after preconditioning) stay one (1, M_pad)
# row; per-device (N, M) tables are tiled like the state.
#
# Service overlay (``slot_values``): the service tier's realized decision
# uses RAW per-slot values (channel power, image cycles, predictor gain)
# while rho and the dual subgradient stay on the quantized tables.  When
# slot-value streams are provided they ride the trace's layout and
# replace the one-hot table gather in the realized decision (gated on
# j > 0, since a raw gain w > 0 can coexist with the null state).
#
# Multi-cloudlet topology (``assoc`` / ``H_k``): the capacity dual
# generalizes from a scalar to a (1, K_pad) row (K padded to the lane
# multiple with H = 0 cloudlets whose dual provably stays 0).  Per slot,
# a device's price is its cloudlet's dual gathered by a one-hot lane mask,
# and the per-cloudlet load reduction is the same mask applied to the
# per-device row loads.  The scalar path is the K = 1 special case.
#
# Binned topology reduction (``topo_binned``, metro-scale K): the one-hot
# mask path materializes an (N, K_pad) fp32 mask PER SLOT — at K = 4096,
# N = 2048 that is 32 MB, past VMEM, and the compare + broadcast-reduce
# runs on the VPU.  The binned variant decomposes a cloudlet id into
# (hi, lo) = (a // 128, a % 128) and keeps the duals / capacities / loads
# in a (K_hi, 128) = (K_pad / 128, 128) layout:
#   gather: tmp = himask @ mu2 -> (N, 128); mu_n = sum(tmp * lomask, 1)
#   scatter: load2 = himask^T @ (rows * lomask) -> (K_hi, 128)
# himask (N, K_hi) and lomask (N, 128) replace the (N, K_pad) mask — mask
# memory drops 128x and the contraction runs on the MXU (at full f32
# precision: the duals must not round through bf16).  Same math, a
# different fp reduction tree — kernel-vs-oracle tests compare with
# allclose tolerances either way.  Selected automatically above
# ``_BINNED_K_THRESHOLD`` cloudlets; K = 1 always takes the scalar path.
# ---------------------------------------------------------------------------

_BINNED_K_THRESHOLD = 512  # auto topo_binned above this many cloudlets

# Largest padded fleet table (N_pad * M_pad fp32 elements) the whole-fleet
# kernel keeps resident: its ~a dozen live (N, M) buffers then fit the
# v5e's default scoped VMEM (twice this is refused by its compiler).
# Larger fleets stream through the tiled kernel in tiles of ``_TILE_N``
# devices, the widest tile that fits the same default.
_WHOLE_FLEET_MAX_ELEMS = 1024 * 128
_TILE_N = 1024


def rollout_block_n(N: int, M: int):
    """The rollout kernel for a fleet of N devices over M states: None
    selects the whole-fleet kernel (state VMEM-resident), an int the
    device-tiled kernel with that many devices per tile."""
    Np = N + (-N % 128)
    Mp = M + (-M % 128)
    return None if Np * Mp <= _WHOLE_FLEET_MAX_ELEMS else _TILE_N


_HIGHEST = jax.lax.Precision.HIGHEST


def _topo_reducers(n_rows, Hk, topo_binned):
    """Build (masks_of(a_col), gather(mu, masks), scatter(rows, masks))
    for the per-slot topology reductions, in either the one-hot-mask or
    the binned (hi, lo) layout (see the module comment)."""
    if topo_binned:
        K_hi = Hk.shape[0]
        hicol = jax.lax.broadcasted_iota(jnp.int32, (n_rows, K_hi), 1)
        locol = jax.lax.broadcasted_iota(jnp.int32, (n_rows, 128), 1)

        def masks_of(a_col):  # a_col (n, 1) int32
            himask = (hicol == a_col // 128).astype(jnp.float32)
            lomask = (locol == a_col % 128).astype(jnp.float32)
            return himask, lomask

        def gather(mu2, masks):  # mu2 (K_hi, 128) -> (n, 1)
            himask, lomask = masks
            tmp = jax.lax.dot_general(
                himask, mu2, (((1,), (0,)), ((), ())), precision=_HIGHEST,
                preferred_element_type=jnp.float32)
            return jnp.sum(tmp * lomask, axis=1, keepdims=True)

        def scatter(rows, masks):  # rows (n, 1) -> (K_hi, 128)
            himask, lomask = masks
            return jax.lax.dot_general(
                himask, rows * lomask, (((0,), (0,)), ((), ())),
                precision=_HIGHEST, preferred_element_type=jnp.float32)
    else:
        K_pad = Hk.shape[1]
        kcol = jax.lax.broadcasted_iota(jnp.int32, (n_rows, K_pad), 1)

        def masks_of(a_col):
            return ((kcol == a_col).astype(jnp.float32),)

        def gather(mu_row, masks):  # mu_row (1, K_pad) -> (n, 1)
            return jnp.sum(mu_row * masks[0], axis=1, keepdims=True)

        def scatter(rows, masks):  # rows (n, 1) -> (1, K_pad)
            return jnp.sum(rows * masks[0], axis=0, keepdims=True)

    return masks_of, gather, scatter


def _column(blk, c):
    """Column ``c`` (static or traced) of a (rows, C) block as (rows, 1):
    a masked lane reduction, exact (every other lane adds 0)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
    return jnp.sum(jnp.where(lane == c, blk, 0), axis=1, keepdims=True)


def _step_size(t, a, beta):
    """(tf, a_t) for global slot ``t`` (int32 scalar): tf = max(t, 1) as a
    (1, 1) fp32 vector, a_t = a / tf^beta (powers run on the vector unit)."""
    tf = jnp.full((1, 1), jnp.maximum(t, 1), jnp.int32).astype(jnp.float32)
    return tf, a / tf**beta


def _slot_update(j_col, now, lam, mu_n, counts, o, h, w, B, tf, a_t, col):
    """One slot of Algorithm 1 for a block of devices.

    ``now`` is the raw (o, h, w) columns of the service overlay, or None
    to gather the realized values from the tables.  Returns (off (n, 1)
    bool, lam', counts', ry) — ``ry`` feeds the caller's load reduction.
    """
    onehot = (col == j_col).astype(jnp.float32)  # (n, M)
    counts = counts + onehot
    rho = counts * (1.0 / tf)
    if now is not None:
        o_now, h_now, w_now = now
        task = j_col > 0
    else:  # the one-hot doubles as the table gather (o_now = o[n, j_n])
        o_now = jnp.sum(o * onehot, axis=1, keepdims=True)
        h_now = jnp.sum(h * onehot, axis=1, keepdims=True)
        w_now = jnp.sum(w * onehot, axis=1, keepdims=True)
        task = True  # the null state's w = 0 already blocks offloading
    # realized decision under (lam_t, mu_t)
    off = (lam * o_now + mu_n * h_now < w_now) & (w_now > 0) & task
    # dual subgradient from the full policy under rho_t
    price = lam * o + mu_n * h
    y = jnp.where((price < w) & (w > 0), 1.0, 0.0)
    ry = rho * y
    g_pow = jnp.sum(o * ry, axis=1, keepdims=True) - B  # (n, 1)
    lam = jnp.maximum(lam + a_t * g_pow, 0.0)
    return off, lam, counts, ry


def _split_refs(refs, has_slots, has_topo, has_lam):
    """Pop the shared operand prefix (scalars, trace, slot values, assoc,
    tables, B, [lam seed], mu seed, state seed, capacities) off a rollout
    kernel's refs; returns them plus the remaining (output, scratch)
    refs."""
    refs = list(refs)
    take = lambda n: [refs.pop(0) for _ in range(n)]
    scal_ref, t0_ref, j_ref = take(3)
    sv_refs = take(3) if has_slots else None
    a_ref = refs.pop(0) if has_topo else None
    o_ref, h_ref, w_ref, b_ref = take(4)
    lam0_ref = refs.pop(0) if has_lam else None
    mu0_ref, counts0_ref = take(2)
    hk_ref = refs.pop(0) if has_topo else None
    return (scal_ref, t0_ref, j_ref, sv_refs, a_ref, o_ref, h_ref, w_ref,
            b_ref, lam0_ref, mu0_ref, counts0_ref, hk_ref, refs)


def _onalgo_chunked_kernel(*refs, chunk, has_slots, has_topo,
                           topo_tv=False, topo_binned=False):
    (scal_ref, t0_ref, j_ref, sv_refs, a_ref, o_ref, h_ref, w_ref, b_ref,
     lam0_ref, mu0_ref, counts0_ref, hk_ref, rest) = _split_refs(
         refs, has_slots, has_topo, has_lam=True)
    off_ref, museq_ref, lnorm_ref, lam_ref, mu_ref, counts_ref = rest
    k = pl.program_id(0)
    t0 = t0_ref[0]  # global slots already consumed (traced resume)
    a, beta, H = scal_ref[0], scal_ref[1], scal_ref[2]

    @pl.when(k == 0)
    def _init():
        lam_ref[...] = lam0_ref[...]
        mu_ref[...] = mu0_ref[...]
        counts_ref[...] = counts0_ref[...]

    o = o_ref[...].astype(jnp.float32)  # (N, M) or a shared (1, M) row
    h = h_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    B = b_ref[...].astype(jnp.float32)  # (N, 1)
    lam = lam_ref[...]  # (N, 1)
    counts = counts_ref[...]  # (N, M)
    col = jax.lax.broadcasted_iota(jnp.int32, counts.shape, 1)
    mu = mu_ref[...]  # (1, 1) scalar dual, (1, K_pad) row or (K_hi, 128)
    if has_topo:
        Hk = hk_ref[...].astype(jnp.float32)
        masks_of, gather, scatter = _topo_reducers(counts.shape[0], Hk,
                                                   topo_binned)
        if not topo_tv:  # static map: one mask set for all slots
            amask = masks_of(a_ref[...])
    # slot streams arrive lane-dense as (C, N) blocks: one transpose per
    # grid step turns each slot into an (N, 1) column
    j_cols = j_ref[...].T  # (N, C)
    sv_cols = None if sv_refs is None else [r[...].T for r in sv_refs]
    a_cols = a_ref[...].T if topo_tv else None
    lane = jax.lax.broadcasted_iota(jnp.int32, j_cols.shape, 1)

    def slot(c, carry):  # a loop, not unrolled: code size is O(1) in C
        lam, mu, counts, off_blk = carry
        tf, a_t = _step_size(k * chunk + (c + 1 + t0), a, beta)
        if has_topo:  # each device priced by its CURRENT cloudlet's dual
            mask = masks_of(_column(a_cols, c)) if topo_tv else amask
            mu_n = gather(mu, mask)  # (N, 1)
        else:
            mu_n = mu
        now = (None if sv_cols is None
               else tuple(_column(x, c) for x in sv_cols))
        off, lam, counts, ry = _slot_update(
            _column(j_cols, c), now, lam, mu_n, counts, o, h, w, B, tf,
            a_t, col)
        off_blk = jnp.where(lane == c, off.astype(jnp.float32), off_blk)
        if has_topo:
            rows = jnp.sum(h * ry, axis=1, keepdims=True)  # (N, 1)
            mu = jnp.maximum(mu + a_t * (scatter(rows, mask) - Hk), 0.0)
        else:
            mu = jnp.maximum(mu + a_t * (jnp.sum(h * ry, keepdims=True)
                                         - H), 0.0)
        museq_ref[c] = mu
        lnorm_ref[c] = jnp.sqrt(jnp.sum(lam * lam, keepdims=True)
                                + jnp.sum(mu * mu, keepdims=True))
        return lam, mu, counts, off_blk

    lam, mu, counts, off_blk = jax.lax.fori_loop(
        0, chunk, slot, (lam, mu, counts, jnp.zeros(lane.shape, jnp.float32)))
    off_ref[...] = off_blk.T
    lam_ref[...] = lam
    mu_ref[...] = mu
    counts_ref[...] = counts


def _pad_fleet(j_seq, lam0, counts0, tables, B, *, n_mult, lam_lane):
    """Shared padding for the whole-simulation kernels.

    States pad to the lane multiple (128) with inert w = 0 columns; devices
    pad to ``n_mult`` rows with B = 0 (their duals provably stay 0 and they
    contribute nothing to any reduction).  A table shared by the fleet
    ((M,)) stays one (1, M_pad) row; an (N, M) table pads like the state.

    ``lam_lane`` packs lam into the state: the visit counts take lanes
    [0, M) of one (N_pad, M_pad) block and lam its last lane, a padding
    state (M_pad > M is then forced) that is never visited and whose
    w = 0 keeps it out of every reduction.  Returns (j, lam column or
    None, state, tables, B, (N_pad, M_pad)).
    """
    T, N = j_seq.shape
    M = counts0.shape[-1]
    M_pad = (M // 128 + 1) * 128 - M if lam_lane else -M % 128
    N_pad = -N % n_mult
    pad_m = lambda x: jnp.pad(x, ((0, 0), (0, M_pad)))
    pad_n = lambda x: jnp.pad(x, ((0, N_pad), (0, 0)))
    tabs = []
    for tab in tables:
        tab = jnp.asarray(tab, jnp.float32)
        tabs.append(pad_m(tab[None, :]) if tab.ndim == 1
                    else pad_n(pad_m(tab)))
    state = pad_n(pad_m(counts0.astype(jnp.float32)))
    lam_p = jnp.pad(lam0.astype(jnp.float32), (0, N_pad))
    if lam_lane:
        state, lam_p = state.at[:, -1].set(lam_p), None
    else:
        lam_p = lam_p[:, None]
    B_p = jnp.pad(jnp.broadcast_to(B, (N,)).astype(jnp.float32),
                  (0, N_pad))[:, None]
    # padded devices sit in no state at all (j = -1): no visit counts, so
    # rho = 0 and they never price, offload or load — inert even against
    # a shared (nonzero) table row
    j_p = jnp.pad(j_seq.astype(jnp.int32), ((0, 0), (0, N_pad)),
                  constant_values=-1)
    return j_p, lam_p, state, tabs, B_p, state.shape


def _stream(x, Np, dtype):
    """(T, N) per-slot stream -> the kernels' (T, N_pad) layout; padded
    devices get 0 (null state / zero values: they never offload)."""
    return jnp.pad(x.astype(dtype), ((0, 0), (0, Np - x.shape[1])))


def _pad_topology(assoc, H_k, mu0, Np, topo_binned):
    """Pad the topology operands to kernel layout.

    A time-varying assoc (T, N) rides the trace's (T, N_pad) layout;
    a static assoc (N,) stays one (N_pad, 1) column (no O(T * N)
    broadcast).  Padded devices point at cloudlet 0 — their zero value
    rows contribute exactly 0 to any load.  H_k / mu0 (K,) become
    (1, K_pad) lane-aligned rows — (K_hi, 128) when binned — padded with
    H = 0 cloudlets no device is associated with, whose dual provably
    stays 0 (load 0, slack 0).  Returns (assoc_arr, hk, mu, n_k, K_pad).
    """
    n_k = H_k.shape[0]
    K_pad = n_k + (-n_k % 128)
    shape = (K_pad // 128, 128) if topo_binned else (1, K_pad)
    hk = jnp.pad(H_k.astype(jnp.float32), (0, K_pad - n_k)).reshape(shape)
    mu = jnp.pad(mu0.astype(jnp.float32), (0, K_pad - n_k)).reshape(shape)
    if assoc.ndim == 1:  # static map: one column
        a_arr = jnp.pad(assoc.astype(jnp.int32),
                        (0, Np - assoc.shape[0]))[:, None]
    else:
        a_arr = _stream(assoc, Np, jnp.int32)
    return a_arr, hk, mu, n_k, K_pad


def _rollout_operands(j_seq, lam0, mu0, counts0, tables, B, H, a, beta, *,
                      chunk, n_mult, t0, slot_values, assoc, H_k,
                      topo_binned, lam_lane):
    """Validate and lay out a rollout's operands (shared by both kernels).

    Returns (args, meta): ``args`` in the kernels' operand order (see
    ``_split_refs``; the lam seed is left out when ``lam_lane`` packs it
    into the state), ``meta`` the static layout facts."""
    T, N = j_seq.shape
    if T % chunk != 0:
        raise ValueError(f"T={T} must be a multiple of chunk={chunk}")
    if (assoc is None) != (H_k is None):
        raise ValueError("assoc and H_k must be passed together")
    K = T // chunk
    j_p, lam_p, state, tabs, B_p, (Np, Mp) = _pad_fleet(
        j_seq, lam0, counts0, tables, B, n_mult=n_mult, lam_lane=lam_lane)
    scal = jnp.stack([jnp.float32(a), jnp.float32(beta),
                      jnp.float32(H if H_k is None else 0.0)])
    t0_arr = jnp.asarray(t0, jnp.int32).reshape(1)
    sv = (() if slot_values is None else
          tuple(_stream(x, Np, jnp.float32) for x in slot_values))
    has_topo = assoc is not None
    if has_topo:
        if topo_binned is None:
            topo_binned = H_k.shape[0] > _BINNED_K_THRESHOLD
        topo_binned = bool(topo_binned)
        a_arr, hk, mu_arr, n_k, Kp = _pad_topology(
            assoc, H_k, mu0, Np, topo_binned)
        topo_in, hk_in = (a_arr,), (hk,)
    else:
        topo_binned, n_k, Kp = False, None, None
        mu_arr = jnp.full((1, 1), mu0, jnp.float32)
        topo_in, hk_in = (), ()
    lam_in = () if lam_lane else (lam_p,)
    args = (scal, t0_arr, j_p, *sv, *topo_in, *tabs, B_p, *lam_in,
            mu_arr, state, *hk_in)
    meta = dict(T=T, N=N, M=counts0.shape[-1], K=K, Np=Np, Mp=Mp,
                has_slots=slot_values is not None, has_topo=has_topo,
                topo_tv=has_topo and assoc.ndim == 2,
                topo_binned=topo_binned, n_k=n_k, Kp=Kp,
                shared=tuple(t.shape[0] == 1 for t in tabs),
                mu_shape=mu_arr.shape, mu_in=len(args) - len(hk_in) - 2)
    return args, meta


def _rollout_outputs(meta, off, mu_seq, lnorm, lam_f, mu_f, counts_f):
    """Un-pad a rollout's outputs to the public contract (``lam_f`` None:
    lam rides the state's last lane)."""
    T, N, M = meta["T"], meta["N"], meta["M"]
    offload = off[:, :N] > 0.5
    lam_f = counts_f[:N, -1] if lam_f is None else lam_f[:N, 0]
    lnorm = lnorm.reshape(T)
    if meta["has_topo"]:
        n_k, Kp = meta["n_k"], meta["Kp"]
        return (offload, mu_seq.reshape(T, Kp)[:, :n_k], lnorm, lam_f,
                mu_f.reshape(Kp)[:n_k], counts_f[:N, :M])
    return (offload, mu_seq.reshape(T), lnorm, lam_f, mu_f.reshape(()),
            counts_f[:N, :M])


def onalgo_chunked_pallas(j_seq, lam0, mu0, counts0, o_tab, h_tab, w_tab,
                          B, H, a, beta, *, chunk=8, t0=0,
                          slot_values=None, assoc=None, H_k=None,
                          topo_binned=None, interpret=True):
    """Fused T-slot OnAlgo rollout, whole fleet VMEM-resident (matches
    kernels/ref.onalgo_chunked_ref).

    j_seq: (T, N) int32 state indices, T a multiple of ``chunk``.
    lam0 (N,), mu0 (), counts0 (N, M): algorithm state entering slot t0+1.
    o/h/w: value tables, (M,) shared or (N, M) per-device, ALREADY in the
      space the duals are updated in (preconditioned by the caller).
    B (N,), H (): constraint RHS in the same space; a, beta: step rule.
    t0: global slot count already consumed (resuming mid-trace).  May be
      a traced int32 scalar — the streaming engines sweep it across slab
      launches under a single compile.
    slot_values: optional (o_now, h_now, w_now) raw per-slot (T, N) value
      streams — the service overlay, ALREADY in the dual space — driving
      the realized decision instead of the table gather (rho and the
      dual subgradient stay on the tables).
    assoc / H_k: optional multi-cloudlet topology — int32 current
      cloudlet ids ((T, N) time-varying, or (N,) static) and (K,)
      capacities (dual space).  mu0 must then be the (K,) dual vector;
      mu outputs gain a trailing K axis.  ``H`` is ignored in this mode
      (the per-cloudlet RHS is H_k).
    topo_binned: use the binned (hi, lo) topology reduction (see the
      module comment) instead of the one-hot (N, K_pad) mask.  None
      (default) auto-selects it for K > _BINNED_K_THRESHOLD.

    Returns (offload (T, N) bool, mu_seq (T,) or (T, K), lam_norm_seq
             (T,), lam (N,), mu () or (K,), counts (N, M)).
    """
    args, m = _rollout_operands(
        j_seq, lam0, mu0, counts0, (o_tab, h_tab, w_tab), B, H, a, beta,
        chunk=chunk, n_mult=128, t0=t0, slot_values=slot_values,
        assoc=assoc, H_k=H_k, topo_binned=topo_binned, lam_lane=False)
    K, Np, Mp = m["K"], m["Np"], m["Mp"]
    const = lambda shape: pl.BlockSpec(shape, lambda k: (0,) * len(shape))
    stream = pl.BlockSpec((chunk, Np), lambda k: (k, 0))
    mu_shape = m["mu_shape"]
    in_specs = [_SMEM, _SMEM, stream]
    in_specs += [stream] * (3 if m["has_slots"] else 0)
    if m["has_topo"]:
        in_specs.append(stream if m["topo_tv"] else const((Np, 1)))
    in_specs += [const((1, Mp) if s else (Np, Mp)) for s in m["shared"]]
    in_specs += [const((Np, 1)), const((Np, 1)), const(mu_shape),
                 const((Np, Mp))]
    if m["has_topo"]:
        in_specs.append(const(mu_shape))
    series = pl.BlockSpec((chunk,) + mu_shape, lambda k: (k, 0, 0))
    kern = functools.partial(_onalgo_chunked_kernel, chunk=chunk,
                             has_slots=m["has_slots"],
                             has_topo=m["has_topo"], topo_tv=m["topo_tv"],
                             topo_binned=m["topo_binned"])
    # Donation-safe carry: lam/mu/counts inputs alias their output
    # buffers (same shapes/dtypes), so a donated caller runs the whole
    # rollout without a second copy of the state.  Safe because the
    # kernel reads the seed blocks only at grid step k == 0, and the
    # constant-index state blocks are flushed to HBM once, at the end.
    mu_in = m["mu_in"]
    outs = pl.pallas_call(
        kern,
        name="onalgo_chunked",
        grid=(K,),
        input_output_aliases={mu_in - 1: 3, mu_in: 4, mu_in + 1: 5},
        in_specs=in_specs,
        out_specs=[stream, series,
                   pl.BlockSpec((chunk, 1, 1), lambda k: (k, 0, 0)),
                   const((Np, 1)), const(mu_shape), const((Np, Mp))],
        out_shape=[
            jax.ShapeDtypeStruct((m["T"], Np), jnp.float32),
            jax.ShapeDtypeStruct((m["T"],) + mu_shape, jnp.float32),
            jax.ShapeDtypeStruct((m["T"], 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((Np, 1), jnp.float32),
            jax.ShapeDtypeStruct(mu_shape, jnp.float32),
            jax.ShapeDtypeStruct((Np, Mp), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return _rollout_outputs(m, *outs)


# ---------------------------------------------------------------------------
# Device-tiled chunked kernel.
#
# The whole-fleet kernel above keeps the fleet's tables and state resident
# in VMEM, which caps N * M.  This variant removes the cap: the grid is
# (K chunks, C slots, n_tiles device tiles) and only one (block_n, M) tile
# of the tables/state is resident per grid step, so VMEM use is
# O(block_n * M) regardless of fleet size.
#
# The cloudlet dual mu couples every device each slot (g_cap sums the load
# over the full fleet), so slots cannot be decoupled across tiles.  Each
# slot therefore runs as a two-phase tile sweep:
#   phase 1 (every tile): rho update, realized decision, tile-local lambda
#     dual ascent, and the tile's PARTIAL load sum, accumulated into a
#     persistent VMEM accumulator;
#   phase 2 (last tile of the slot): the mu reduction — g_cap from the
#     accumulated load, one dual-ascent step on mu, and the ||(lam, mu)||
#     series entry from the accumulated lambda norms.
# mu lives in a constant-index output block (VMEM-resident for the whole
# kernel) so phase 2's update is visible to every tile of the next slot.
#
# Per-tile state — lam and the visit counts, packed into one lane-dense
# (block_n, M_pad) block (counts in lanes [0, M), lam in the last lane) —
# is revisited every n_tiles grid steps.  The TPU's block pipeline writes
# an output block back to HBM when the sweep moves on but never fetches
# it again, so the state does not ride BlockSpecs: it stays in HBM
# (``pl.ANY``; the state seed aliases the state output) and the kernel
# streams each tile through a double-buffered VMEM scratch with explicit
# async copies (whose HBM slices must be 128 lanes wide — hence the
# packing): while step g computes tile i, the copy-in of step g + 1's
# tile runs, after step g - 1's copy-out of the other buffer has landed.
# A tile's next visit is n_tiles >= 2 steps later (a single-tile fleet is
# padded with an inert second tile), so its copy-out has always been
# waited on before its copy-in starts.  Each slot's decisions are written
# exactly once, so they are an ordinary pipelined output: one
# (1, 1, block_n) block of the (T, 1, N_pad) decision array per step.
# The grid runs in order (slot-major, tiles minor): every dimension is
# "arbitrary".
# ---------------------------------------------------------------------------


def _onalgo_tiled_kernel(*refs, chunk, n_tiles, block_n, has_slots,
                         has_topo, topo_tv=False, topo_binned=False):
    (scal_ref, t0_ref, j_ref, sv_refs, a_ref, o_ref, h_ref, w_ref, b_ref,
     _, mu0_ref, _, hk_ref, rest) = _split_refs(
         refs, has_slots, has_topo, has_lam=False)
    (off_ref, museq_ref, lnorm_ref, mu_ref, state_hbm,
     st_buf, sems, load_acc, lam2_acc) = rest
    k, c, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    t0 = t0_ref[0]  # global slots already consumed (traced resume)
    a, beta, H = scal_ref[0], scal_ref[1], scal_ref[2]
    g = (k * chunk + c) * n_tiles + i  # linear grid step
    n_steps = pl.num_programs(0) * chunk * n_tiles
    cur = g % 2

    def copy(tile, slot, direction):
        """The state copy of one tile between HBM and VMEM buffer
        ``slot``; direction 0 = in, 1 = out."""
        rows = pl.ds(pl.multiple_of(tile * block_n, block_n), block_n)
        pair = (state_hbm.at[rows], st_buf.at[slot])
        return pltpu.make_async_copy(*(pair if direction == 0
                                       else pair[::-1]),
                                     sems.at[direction, slot])

    @pl.when(g == 0)
    def _first():
        mu_ref[...] = mu0_ref[...]
        copy(i, cur, 0).start()

    copy(i, cur, 0).wait()

    # prefetch step g + 1's tile into the other buffer, once step g - 1's
    # copy-out from it has landed
    @pl.when(g + 1 < n_steps)
    def _prefetch():
        @pl.when(g > 0)
        def _():
            copy(i, 1 - cur, 1).wait()
        copy(jnp.where(i + 1 == n_tiles, 0, i + 1), 1 - cur, 0).start()

    o = o_ref[...].astype(jnp.float32)  # (bn, M) or a shared (1, M) row
    h = h_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    B = b_ref[...].astype(jnp.float32)  # (bn, 1)
    state = st_buf[cur]  # (bn, M_pad): counts, lam in the last lane
    col = jax.lax.broadcasted_iota(jnp.int32, state.shape, 1)
    lam_lane = state.shape[1] - 1
    lam = _column(state, lam_lane)  # (bn, 1)
    tf, a_t = _step_size(k * chunk + (c + 1 + t0), a, beta)

    # --- phase 1: tile-local slot step under (lam_tile, mu_t)
    mu = mu_ref[...]  # mu_t: written by the previous slot's phase 2
    if has_topo:
        Hk = hk_ref[...].astype(jnp.float32)
        masks_of, gather, scatter = _topo_reducers(block_n, Hk, topo_binned)
        a_col = (_column(a_ref[...].T, c) if topo_tv
                 else a_ref[...])  # (bn, 1)
        amask = masks_of(a_col)
        mu_n = gather(mu, amask)  # (bn, 1)
    else:
        mu_n = mu
    # slot streams arrive lane-dense as (C, block_n) blocks
    now = (None if sv_refs is None
           else tuple(_column(r[...].T, c) for r in sv_refs))
    # the lam lane is a padding state (w = 0): it never enters a reduction
    off, lam_new, counts, ry = _slot_update(
        _column(j_ref[...].T, c), now, lam, mu_n, state, o, h, w, B, tf, a_t,
        col)
    st_buf[cur] = jnp.where(col == lam_lane, lam_new, counts)
    copy(i, cur, 1).start()
    # this slot's decisions, as a lane-dense (1, block_n) row
    off_ref[0] = jnp.broadcast_to(off.astype(jnp.float32),
                                  (block_n, 128)).T[:1]

    @pl.when(i == 0)
    def _reset_acc():
        load_acc[...] = jnp.zeros_like(load_acc)
        lam2_acc[...] = jnp.zeros_like(lam2_acc)

    if has_topo:
        rows = jnp.sum(h * ry, axis=1, keepdims=True)  # (bn, 1)
        load_acc[...] += scatter(rows, amask)
    else:
        load_acc[...] += jnp.sum(h * ry, keepdims=True)
    lam2_acc[...] += jnp.sum(lam_new * lam_new, keepdims=True)

    # --- phase 2: the mu reduction, once the last tile's partials are in
    @pl.when(i == n_tiles - 1)
    def _mu_reduce():
        rhs = Hk if has_topo else H
        mu_new = jnp.maximum(mu + a_t * (load_acc[...] - rhs), 0.0)
        mu_ref[...] = mu_new
        museq_ref[0] = mu_new
        lnorm_ref[0] = jnp.sqrt(lam2_acc[...]
                                + jnp.sum(mu_new * mu_new, keepdims=True))

    @pl.when(g + 1 == n_steps)
    def _drain():  # the last two steps' copy-outs are still in flight
        copy(i, 1 - cur, 1).wait()
        copy(i, cur, 1).wait()


def onalgo_tiled_pallas(j_seq, lam0, mu0, counts0, o_tab, h_tab, w_tab,
                        B, H, a, beta, *, chunk=8, block_n=256, t0=0,
                        slot_values=None, assoc=None, H_k=None,
                        topo_binned=None, interpret=True):
    """Device-tiled fused OnAlgo rollout — same contract and results as
    ``onalgo_chunked_pallas`` (and ``kernels/ref.onalgo_chunked_ref``),
    including the service-overlay ``slot_values`` streams and the
    multi-cloudlet ``assoc`` / ``H_k`` topology (the two-phase sync then
    accumulates a row of per-cloudlet tile partials instead of one
    scalar), but VMEM use is O(block_n * M) instead of O(N * M): fleets of
    any size run chunked without sharding first.

    block_n: devices per tile (a multiple of 128 on the chip, where it is
      a lane dim; of 8 interpreted); N is padded to it (and to at least
      two tiles) with inert rows.  See the section comment above for the
      two-phase mu sync and the streamed state.
    """
    if block_n % (8 if interpret else 128) != 0:
        raise ValueError(f"block_n={block_n} must be a multiple of 8 "
                         "(of 128 on the chip: it is a lane dim there)")
    N = j_seq.shape[1]
    n_mult = block_n * (2 if N <= block_n else 1)  # >= 2 tiles
    args, m = _rollout_operands(
        j_seq, lam0, mu0, counts0, (o_tab, h_tab, w_tab), B, H, a, beta,
        chunk=chunk, n_mult=n_mult, t0=t0, slot_values=slot_values,
        assoc=assoc, H_k=H_k, topo_binned=topo_binned, lam_lane=True)
    K, Np, Mp = m["K"], m["Np"], m["Mp"]
    n_tiles = Np // block_n
    mu_shape = m["mu_shape"]
    const = lambda shape: pl.BlockSpec(shape,
                                       lambda k, c, i: (0,) * len(shape))
    tile = lambda width: pl.BlockSpec((block_n, width),
                                      lambda k, c, i: (i, 0))
    stream = pl.BlockSpec((chunk, block_n), lambda k, c, i: (k, i))
    slot = lambda shape: pl.BlockSpec(
        (1,) + shape, lambda k, c, i: (k * chunk + c, 0, 0))
    in_specs = [_SMEM, _SMEM, stream]
    in_specs += [stream] * (3 if m["has_slots"] else 0)
    if m["has_topo"]:
        in_specs.append(stream if m["topo_tv"] else tile(1))
    in_specs += [const((1, Mp)) if s else tile(Mp) for s in m["shared"]]
    in_specs += [tile(1), const(mu_shape), _HBM]
    if m["has_topo"]:
        in_specs.append(const(mu_shape))
    kern = functools.partial(_onalgo_tiled_kernel, chunk=chunk,
                             n_tiles=n_tiles, block_n=block_n,
                             has_slots=m["has_slots"],
                             has_topo=m["has_topo"], topo_tv=m["topo_tv"],
                             topo_binned=m["topo_binned"])
    off, mu_seq, lnorm, mu_f, state_f = pl.pallas_call(
        kern,
        name="onalgo_tiled",  # the trace's %onalgo_tiled: keep it stable
        grid=(K, chunk, n_tiles),
        # the HBM buffer the state tiles stream through starts out
        # holding the seed
        input_output_aliases={m["mu_in"] + 1: 4},
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, 1, block_n),
                                lambda k, c, i: (k * chunk + c, 0, i)),
                   slot(mu_shape), slot((1, 1)), const(mu_shape), _HBM],
        out_shape=[
            jax.ShapeDtypeStruct((m["T"], 1, Np), jnp.float32),
            jax.ShapeDtypeStruct((m["T"],) + mu_shape, jnp.float32),
            jax.ShapeDtypeStruct((m["T"], 1, 1), jnp.float32),
            jax.ShapeDtypeStruct(mu_shape, jnp.float32),
            jax.ShapeDtypeStruct((Np, Mp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, block_n, Mp), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM(mu_shape, jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return _rollout_outputs(m, off.reshape(m["T"], Np), mu_seq, lnorm,
                            None, mu_f, state_f)
