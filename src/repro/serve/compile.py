"""Compile the end-to-end service simulation to the core fleet contract.

The paper's headline experiments (Figs. 5-8) run the *service* tier:
trained classifier pairs, the measured power curve, the gain predictor,
and per-slot cloudlet admission.  Historically that was a pure-Python
``for t in range(T)`` loop with one jitted step per slot.  This module
lowers a ``(SimConfig, PrecomputedPool)`` pair to the same
``(Trace, tables, params)`` contract the fleet engine consumes — plus a
:class:`~repro.core.fleet.RawOverlay` of raw per-slot values — so the
whole horizon runs as ONE scanned (or chunked/sharded) fleet rollout:

  * the image stream, Markov channel, and bursty arrivals come from the
    workload layer (:mod:`repro.workload`) under the versioned RNG
    contract ``sim.rng_version`` (v1, counter-based streams — the only
    live contract), jitted end to end on device;
  * raw (o, h, w) values are quantized into the pool-calibrated state
    space in one fused call => the (T, N) ``Trace``;
  * raw values, plus the local/cloudlet correctness of each sampled
    image, ride along in the overlay so decisions and accounting match
    the service semantics exactly (rho alone uses the quantized index).

At fleet scale the (T, N) arrays themselves are the ceiling:
``compile_service_streaming`` lowers the same run to a
:class:`StreamingService` whose jitted ``slab(t0, L)`` produces any
horizon slab — trace and overlay — bit-identical to the materialized
arrays, from O(L * N) work, for the ``fleet.*_stream`` engines.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core.fleet import RawOverlay, Trace
from repro.core.onalgo import OnAlgoParams, StepRule, risk_adjusted_gain
from repro.core.state_space import StateSpace
from repro.serve.admission import nearest_level
from repro.workload import (StreamingWorkload, generate_service_workload,
                            lower_service_workload, validate_rng_version)


@dataclasses.dataclass
class CompiledService:
    """A service run lowered to the fleet-engine contract.

    ``trace`` / ``tables`` / ``params`` / ``overlay`` feed
    ``fleet.simulate(..., overlay=...)`` (or the chunked/sharded engines)
    verbatim; ``space`` is the pool-calibrated quantized state space
    behind ``trace.j_idx``; ``on`` is the realized (T, N) arrival matrix
    (useful for replaying the same workload through other tiers).
    """

    sim: "SimConfig"  # noqa: F821 — forward ref, defined in simulator.py
    space: StateSpace
    trace: Trace
    tables: Tuple[jax.Array, jax.Array, jax.Array]
    params: OnAlgoParams
    overlay: RawOverlay
    on: np.ndarray
    gain_source: object = None  # repro.gain.GainSource (None = pool tables)

    @property
    def rule(self) -> StepRule:
        return StepRule.inv_sqrt(self.sim.step_a)

    def simulate_args(self):
        """Positional args for ``fleet.simulate(trace, tables, params, ...)``."""
        return self.trace, self.tables, self.params


# Tables of at most this many entries (one lane width) are looked up by a
# select tree; longer ones keep XLA's gather (``lookup``).
SELECT_LOOKUP_MAX = 128


def lookup(idx, tables):
    """``tuple(t[idx] for t in tables)`` for (S,) tables that share one
    integer index array, bit for bit.

    For S <= ``SELECT_LOOKUP_MAX`` a binary select tree on the index's
    bits: each table's entries are paired level by level, one ``where``
    per pair on the level's bit, S - 1 selects in all; the bit tests
    are shared by every table.  The ops are elementwise over ``idx``,
    so XLA fuses them into lane-dense loops (into the consumers, mostly)
    and never materializes an (..., S) one-hot; a select keeps every bit
    (``-0.0`` too, which a one-hot sum would not).  XLA's TPU gather
    issues one element per index, about a thousand times below HBM
    bandwidth on tables this small.  (A chain of ``idx == s`` selects
    gives the same bits, but XLA materializes its S shared compares as
    (L, N) masks.)  The index is first clamped to S - 1, so an index at
    or above S - 1 reads that entry as the gather's clamp would; every
    producer (``levels_from_uniform``) emits indices in [0, S).

    The tree costs O(S) an element and the gather does not grow with S,
    so a longer table (a pool built from a real test set) keeps the
    gather; the choice is fixed by the static table length.
    """
    S = tables[0].shape[0]
    if S > SELECT_LOOKUP_MAX:
        return tuple(t[idx] for t in tables)
    idx = jnp.minimum(idx, S - 1)
    bits = [((idx >> b) & 1) == 1 for b in range((S - 1).bit_length())]
    outs = []
    for t in tables:
        level = [t[s] for s in range(S)]
        for bit in bits:
            # a lone last entry has this bit clear for every index reaching it
            level = [jnp.where(bit, level[i + 1], level[i])
                     if i + 1 < len(level) else level[i]
                     for i in range(0, len(level), 2)]
        outs.append(jnp.broadcast_to(level[0], idx.shape))
    return tuple(outs)


def _lower_values(wl, space, on_override, o_levels, cycles, phi_hat,
                  sigma, d_local, corr_local, corr_cloud, v_risk,
                  zeta_pen):
    """Raw-value lookups + quantization for a realized workload (whole
    horizon or slab) — the one definition both the materialized and the
    streaming lowerings go through, so their outputs are bit-identical.

    Every step applied to a looked-up value is elementwise, and float32
    elementwise ops commute exactly with a lookup, so the gain, the
    ``zeta_pen`` clip and the nearest-level quantization are folded
    into the (S,) pool and (num_rates,) rate tables first; the (L, N)
    slab then only looks values up (``lookup``).  The state index
    splits by ``StateSpace.encode``'s affine layout into a rate part
    and an image part, joined by an exact integer add.

    Returns (on, j_idx, o, h, w, correct_local, correct_cloud, d_local).
    ``zeta_pen`` is the P3 delay penalty (0 disables it exactly:
    clip(w - 0, 0, 1) == w for w already in [0, 1]).  ``on_override``
    replaces the generated arrivals when not None — the image and
    channel streams are unaffected (counter addressing has no
    draw-order coupling).
    """
    with jax.named_scope(spans.VALUE_LOWERING):
        on = wl.on if on_override is None else on_override
        w_tab = risk_adjusted_gain(phi_hat, sigma, v_risk)
        w_tab = jnp.clip(w_tab - zeta_pen, 0.0, 1.0)
        io = nearest_level(o_levels, space.o_levels)
        ih = nearest_level(cycles, space.h_levels)
        iw = nearest_level(w_tab, space.w_levels)
        # encode(io, ih, iw) == encode(io, 0, 0) + encode(0, ih, iw)
        #                       - encode(0, 0, 0)
        jo_tab = space.encode(io, 0, 0).astype(jnp.int32)
        jhw_tab = (space.encode(0, ih, iw)
                   - space.encode(0, 0, 0)).astype(jnp.int32)
        o_raw, jo = lookup(wl.rates, (o_levels, jo_tab))
        h_raw, w_raw, c_local, c_cloud, d_loc, jhw = lookup(
            wl.img, (cycles, w_tab, corr_local, corr_cloud, d_local,
                     jhw_tab))
        j = jnp.where(jnp.asarray(on, bool), jo + jhw, jnp.int32(0))
        return on, j, o_raw, h_raw, w_raw, c_local, c_cloud, d_loc


@partial(jax.jit,
         static_argnames=("T", "N", "pool_size", "num_rates", "burst_len",
                          "space"))
def _compile_v1(seed, T, N, pool_size, num_rates, burst_len, mean_gap,
                space, on_override, o_levels, cycles, phi_hat, sigma,
                d_local, corr_local, corr_cloud, v_risk, zeta_pen):
    """The whole v1 lowering as ONE fused device pass: counter-based
    workload generation, raw-value lookups, and state quantization."""
    wl = generate_service_workload(seed, T, N, pool_size, num_rates,
                                   burst_len, mean_gap)
    return _lower_values(wl, space, on_override, o_levels, cycles, phi_hat,
                         sigma, d_local, corr_local, corr_cloud, v_risk,
                         zeta_pen)


def _pool_device_arrays(pool, fp):
    """float32 device copies of the pool tables, cached on the pool object
    under its content fingerprint (compile_service is called per run; the
    pool is reused across runs)."""
    cache = getattr(pool, "_f32_cache", None)
    if cache is None or cache[0] != fp:
        arrays = tuple(jnp.asarray(x, jnp.float32)
                       for x in (pool.cycles, pool.phi_hat, pool.sigma,
                                 pool.d_local, pool.local_correct,
                                 pool.cloud_correct))
        cache = pool._f32_cache = (fp, arrays)
    return cache[1]


@lru_cache(maxsize=None)
def _space_tables(space: StateSpace):
    """Per-space value tables, built once (StateSpace is frozen)."""
    return space.tables()


def _service_inputs(sim, pool, gain_source=None):
    """Shared pieces of both lowerings: validated contract, calibrated
    space/tables/params, device pool arrays, scalar knobs.

    ``gain_source`` (a :class:`~repro.gain.GainSource`, or None for the
    pool-table default) picks the per-image (phi_hat, sigma) tables that
    enter the fused value lowering, and the state space calibrated to
    them; everything else — cycles, correctness, d_local — always comes
    from the pool.  ``None`` and ``TableGain()`` hit the identical
    cached device arrays, so the default path is byte-for-byte today's.
    """
    from repro.serve.simulator import (RATES, pool_fingerprint, pool_space,
                                       power_of_rate)

    validate_rng_version(sim.rng_version)
    base = _pool_device_arrays(pool, pool_fingerprint(pool))
    if gain_source is None:
        space = pool_space(pool, num_w=sim.num_w_levels, v_risk=sim.v_risk)
        phi, sig = base[1], base[2]
    else:
        from repro.gain.source import as_gain_source
        gain_source = as_gain_source(gain_source)
        gt = gain_source.tables(pool, sim)
        space = gain_source.space(pool, sim)
        phi = jnp.asarray(gt.phi_hat, jnp.float32)
        sig = jnp.asarray(gt.sigma, jnp.float32)
        if phi.shape != base[1].shape or sig.shape != base[2].shape:
            raise ValueError(
                f"gain source resolved tables of shape {phi.shape}/"
                f"{sig.shape}; pool has {base[1].shape} images")
    arrays = ((jnp.asarray(power_of_rate(RATES), jnp.float32),)
              + (base[0], phi, sig) + base[3:])
    params = OnAlgoParams(B=jnp.full((sim.num_devices,), sim.B_n,
                                     jnp.float32),
                          H=jnp.float32(sim.H))
    knobs = (jnp.float32(sim.v_risk),
             jnp.float32(sim.zeta * (sim.d_tr + sim.d_pr_cloud)))
    return space, arrays, params, knobs, len(RATES)


def compile_service(sim, pool, on: Optional[np.ndarray] = None, *,
                    gain_source=None) -> CompiledService:
    """Lower (SimConfig, PrecomputedPool) to a :class:`CompiledService`.

    Workload generation, value lookups, and quantization run as one
    fused jitted device pass over counter-based streams (RNG contract
    v1, the only live one) — no per-slot host loop anywhere.

    ``on``: optional (T, N) bool arrival matrix overriding the built-in
    bursty traffic — e.g. ``CompiledScenario.task_mask()`` from the
    scenario engine, so the service tier replays fleet-tier workloads.

    ``gain_source``: optional :class:`~repro.gain.GainSource` supplying
    the per-image (phi_hat, sigma) tables behind the fused value
    lowering (None = the pool's own tables, bit for bit).
    """
    N, T = sim.num_devices, sim.T
    S = len(pool.local_correct)
    space, arrays, params, knobs, num_rates = _service_inputs(
        sim, pool, gain_source)

    if on is not None:
        on = np.asarray(on, bool)
        if on.shape != (T, N):
            raise ValueError(f"arrival matrix shape {on.shape} != {(T, N)}")

    on_dev, j, o_raw, h_raw, w_raw, c_local, c_cloud, d_loc = (
        _compile_v1(sim.seed, T, N, S, num_rates, tuple(sim.burst_len),
                    sim.mean_gap, space,
                    None if on is None else jnp.asarray(on),
                    *arrays, *knobs))
    on = np.asarray(on_dev, bool)

    trace = Trace(j_idx=jnp.asarray(j, jnp.int32),
                  d_local=jnp.asarray(d_loc, jnp.float32))
    overlay = RawOverlay(
        o=jnp.asarray(o_raw, jnp.float32),
        h=jnp.asarray(h_raw, jnp.float32),
        w=jnp.asarray(w_raw, jnp.float32),
        correct_local=jnp.asarray(c_local, jnp.float32),
        correct_cloud=jnp.asarray(c_cloud, jnp.float32))
    return CompiledService(sim=sim, space=space, trace=trace,
                           tables=_space_tables(space), params=params,
                           overlay=overlay, on=on, gain_source=gain_source)


@partial(jax.jit, static_argnames=("space", "length", "aligned"))
def _service_slab(wl: StreamingWorkload, space, t0, length, o_levels,
                  cycles, phi_hat, sigma, d_local, corr_local, corr_cloud,
                  v_risk, zeta_pen, aligned: bool = False):
    """One fused device pass from counters to a service slab: workload
    slab -> per-entry fold and quantization -> lookups, slots
    [t0, t0 + length).

    ``aligned`` promises ``t0 % ROW_BLOCK == 0`` and generates one fewer
    covering uniform block per slab (see ``StreamingWorkload.slab``)."""
    return _lower_values(wl.slab(t0, length, aligned=aligned), space,
                         None, o_levels, cycles, phi_hat, sigma, d_local,
                         corr_local, corr_cloud, v_risk, zeta_pen)


@partial(jax.jit, static_argnames=("space", "length", "n_cols"))
def _service_slab_cols(wl: StreamingWorkload, space, t0, length, n0,
                       n_cols, o_levels, cycles, phi_hat, sigma, d_local,
                       corr_local, corr_cloud, v_risk, zeta_pen):
    """Column-addressed form of ``_service_slab``: only device columns
    [n0, n0 + n_cols), bit-identical to slicing the full-width slab."""
    return _lower_values(wl.slab_cols(t0, length, n0, n_cols), space,
                         None, o_levels, cycles, phi_hat, sigma, d_local,
                         corr_local, corr_cloud, v_risk, zeta_pen)


@dataclasses.dataclass
class StreamingService:
    """A service run lowered to chunk-addressable (streaming) form.

    Instead of (T, N) trace/overlay arrays, holds the
    :class:`~repro.workload.streaming.StreamingWorkload` boundary states
    plus the device pool tables; ``slab(t0, L)`` produces the
    ``(j_idx, RawOverlay)`` slab for any [t0, t0 + L) — bit-identical
    to the corresponding slices of ``compile_service``'s arrays — which
    is exactly the ``source`` contract of the ``fleet.*_stream``
    engines.  Peak memory: O(L * N), never O(T * N).
    """

    sim: "SimConfig"  # noqa: F821 — forward ref, defined in simulator.py
    space: StateSpace
    tables: Tuple[jax.Array, jax.Array, jax.Array]
    params: OnAlgoParams
    wl: StreamingWorkload
    arrays: tuple  # (o_levels, cycles, phi_hat, sigma, d_local, cl, cc)
    knobs: tuple  # (v_risk, zeta_pen) traced scalars
    gain_source: object = None  # repro.gain.GainSource (None = pool tables)

    @property
    def rule(self) -> StepRule:
        return StepRule.inv_sqrt(self.sim.step_a)

    def slab(self, t0, length: int):
        """(j_idx (L, N) int32, RawOverlay slab) for [t0, t0 + length)."""
        _, j, o_raw, h_raw, w_raw, c_local, c_cloud, _ = _service_slab(
            self.wl, self.space, t0, length, *self.arrays, *self.knobs)
        return j, RawOverlay(o=o_raw, h=h_raw, w=w_raw,
                             correct_local=c_local, correct_cloud=c_cloud)

    def slab_aligned(self, t0, length: int):
        """``slab`` for block-aligned starts: requires ``t0 % ROW_BLOCK
        == 0`` (the caller's burden — t0 may be traced) and generates
        one fewer covering uniform block per slab, bit-identical to
        ``slab``.  The pipelined chunked engine routes its main-loop
        slabs here (``source_aligned=``) when start and slab length are
        block-aligned."""
        _, j, o_raw, h_raw, w_raw, c_local, c_cloud, _ = _service_slab(
            self.wl, self.space, t0, length, *self.arrays, *self.knobs,
            aligned=True)
        return j, RawOverlay(o=o_raw, h=h_raw, w=w_raw,
                             correct_local=c_local, correct_cloud=c_cloud)

    def slab_cols(self, t0, length: int, n0, n_cols: int):
        """Device columns [n0, n0 + n_cols) of ``slab(t0, length)``,
        bit-identical to slicing it, from O(length * n_cols) work — the
        ``source_cols`` contract of ``fleet.simulate_sharded_stream``,
        so each shard generates only its own devices' workload."""
        _, j, o_raw, h_raw, w_raw, c_local, c_cloud, _ = _service_slab_cols(
            self.wl, self.space, t0, length, n0, n_cols, *self.arrays,
            *self.knobs)
        return j, RawOverlay(o=o_raw, h=h_raw, w=w_raw,
                             correct_local=c_local, correct_cloud=c_cloud)


def compile_service_streaming(sim, pool, *,
                              gain_source=None) -> StreamingService:
    """Lower (SimConfig, PrecomputedPool) to a :class:`StreamingService`.

    The only O(T)-sized work is the workload layer's boundary-state
    lowering (one jitted scan over ROW_BLOCK-aligned blocks, O(T/64 * N)
    output); nothing (T, N)-sized is ever materialized.  Arrival
    overrides need the materialized path — use ``compile_service``.
    ``gain_source`` as in :func:`compile_service`: the resolved (S,)
    tables ride in ``arrays``, so every slab — full-width, aligned, or
    column-addressed — looks up the same source.
    """
    space, arrays, params, knobs, num_rates = _service_inputs(
        sim, pool, gain_source)
    wl = lower_service_workload(sim.seed, sim.T, sim.num_devices,
                                len(pool.local_correct), num_rates,
                                tuple(sim.burst_len), sim.mean_gap)
    return StreamingService(sim=sim, space=space,
                            tables=_space_tables(space), params=params,
                            wl=wl, arrays=arrays, knobs=knobs,
                            gain_source=gain_source)


def service_metrics(sim, series) -> dict:
    """Fold fleet-engine series into the service-tier aggregate metrics
    (same keys and semantics as the legacy slot loop)."""
    tasks_raw = float(np.sum(np.asarray(series["tasks"])))
    tasks = max(tasks_raw, 1.0)
    admits = float(np.sum(np.asarray(series["admits"])))
    # every task pays local processing; admitted ones add transmit + cloudlet
    delay = sim.d_pr_dev * tasks_raw + (sim.d_tr + sim.d_pr_cloud) * admits
    mu_seq = np.asarray(series["mu"])
    return {
        "accuracy": float(np.sum(np.asarray(series["correct"]))) / tasks,
        "offload_frac": float(np.sum(np.asarray(series["offloads"]))) / tasks,
        "admit_frac": admits / tasks,
        "avg_power_per_dev": (float(np.sum(np.asarray(series["power"])))
                              / (sim.num_devices * sim.T)),
        "avg_load": float(np.sum(np.asarray(series["load"]))) / sim.T,
        "avg_delay_ms": 1e3 * delay / tasks,
        "tasks": tasks,
        "mu_final": (float(mu_seq[-1])
                     if sim.algo == "onalgo" and mu_seq.size else 0.0),
    }
