"""Gain sources: where the offloading-gain estimate comes from.

The paper's devices offload only when they *predict* a significant
accuracy gain (eq. 1: w = phi_hat - v * sigma); the companion paper
(arXiv:2003.03588) formalizes the predictor-driven variant.  This module
makes that estimate a first-class, swappable component: a
:class:`GainSource` resolves to the per-image ``(phi_hat, sigma)``
tables that enter the ONE fused value lowering
(``serve.compile._lower_values``) every engine consumes — the scanned
fleet, both Pallas kernels' ``slot_values`` streams, the streaming slab
paths, and the live gateway all sit ABOVE the tables, so swapping the
source never touches an engine.

Three sources:

  :class:`TableGain` — the pool's own phi_hat/sigma tables (the oracle
    when the pool carries true gains).  Resolves to the identical cached
    device arrays the default ``gain_source=None`` path uses, so it is
    bit-identical to today's decision streams by construction.

  :class:`OverlayGain` — the RawOverlay raw-value path: the risk
    adjustment is pre-folded into a single raw gain table
    (``w = clip(phi - v*sigma, 0, 1)``, sigma = 0 downstream).  Because
    :func:`~repro.core.onalgo.risk_adjusted_gain` is elementwise it
    commutes exactly with the per-slot image lookup — the overlay ``w``
    stream is bit-identical to the table source's on every engine.

  :class:`ModelGain` — a trained predictor (closed-form ridge or a tiny
    SSM sequence head; see :mod:`repro.gain.model`) whose pure jitted
    inference fills the tables from the pool images' local-classifier
    probabilities, optionally snapped onto a ``num_w_levels``-point gain
    grid.  ``to_pool_tables()`` freezes the predictions back into a
    :class:`~repro.serve.simulator.PrecomputedPool`, and
    ``TableGain`` over that frozen pool round-trips bit-identically.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.onalgo import risk_adjusted_gain


class GainTables(NamedTuple):
    """Resolved per-image gain tables, float32, shape (S,) each."""

    phi_hat: jax.Array
    sigma: jax.Array


class GainSource:
    """Frozen interface: a source of the per-image gain-table pair.

    Implementations are frozen dataclasses.  Contract:

      * ``tables(pool, sim)`` -> :class:`GainTables` — float32 (S,)
        device arrays congruent with the pool;
      * ``space(pool, sim)`` -> the :class:`StateSpace` calibrated to
        those tables (w grid covering the realized gain distribution);
      * ``to_pool_tables(pool, sim)`` -> a new ``PrecomputedPool`` with
        the resolved tables frozen in (float64 copies of the exact
        float32 values, so a ``TableGain`` over the frozen pool resolves
        to bit-identical device arrays and re-derives the identical
        space).

    Resolution happens ONCE per compile (``serve.compile``); the engines
    only ever see the resolved tables.
    """

    def tables(self, pool, sim) -> GainTables:
        raise NotImplementedError

    def space(self, pool, sim):
        """Default: calibrate to the resolved tables (float64, the same
        arithmetic ``pool_space`` applies to a pool's own arrays)."""
        from repro.serve.simulator import calibrated_space
        gt = self.tables(pool, sim)
        return calibrated_space(np.asarray(gt.phi_hat, np.float64),
                                np.asarray(gt.sigma, np.float64),
                                num_w=sim.num_w_levels, v_risk=sim.v_risk)

    def to_pool_tables(self, pool, sim):
        """Freeze the resolved tables into a new pool (all other arrays
        shared) — a trained model exported back to the oracle format."""
        gt = self.tables(pool, sim)
        return dataclasses.replace(
            pool, phi_hat=np.asarray(gt.phi_hat, np.float64),
            sigma=np.asarray(gt.sigma, np.float64))


@dataclasses.dataclass(frozen=True)
class TableGain(GainSource):
    """The pool's phi_hat/sigma tables verbatim (today's path, the
    oracle).  Identical cached device arrays as ``gain_source=None``."""

    def tables(self, pool, sim) -> GainTables:
        from repro.serve.compile import _pool_device_arrays
        from repro.serve.simulator import pool_fingerprint
        base = _pool_device_arrays(pool, pool_fingerprint(pool))
        return GainTables(base[1], base[2])

    def space(self, pool, sim):
        from repro.serve.simulator import pool_space
        return pool_space(pool, num_w=sim.num_w_levels, v_risk=sim.v_risk)


@jax.jit
def _fold_risk(phi, sigma, v_risk):
    return risk_adjusted_gain(phi, sigma, v_risk), jnp.zeros_like(sigma)


@dataclasses.dataclass(frozen=True)
class OverlayGain(GainSource):
    """Risk pre-folded into one raw gain table (sigma = 0 downstream).

    The same float32 ops :func:`risk_adjusted_gain` applies inside the
    fused lowering are applied to the whole (S,) table up front;
    elementwise ops commute exactly with the per-slot lookup, and
    ``w - v*0`` then ``clip`` are bitwise identities on values already
    in [0, 1] — so the overlay's raw ``w`` stream, and therefore every
    decision, is bit-identical to the table source.  The state space
    stays pool-calibrated (same realized distribution).
    """

    def tables(self, pool, sim) -> GainTables:
        base = TableGain().tables(pool, sim)
        phi, sig = _fold_risk(base.phi_hat, base.sigma,
                              jnp.float32(sim.v_risk))
        return GainTables(phi, sig)

    def space(self, pool, sim):
        from repro.serve.simulator import pool_space
        return pool_space(pool, num_w=sim.num_w_levels, v_risk=sim.v_risk)


@partial(jax.jit, static_argnames=("num_levels",))
def snap_to_grid(values, num_levels: int, hi):
    """Snap float32 values onto a uniform ``num_levels``-point grid over
    [0, hi] — nearest level, fp32 distance argmin (the same idiom as
    ``quantize_states_device``).  Grid values are returned exactly, so
    snapped tables survive a float64 pool round trip bit for bit."""
    levels = jnp.linspace(0.0, hi, num_levels).astype(jnp.float32)
    idx = jnp.argmin(jnp.abs(values[:, None] - levels[None, :]), axis=1)
    return levels[idx]


@dataclasses.dataclass(frozen=True, eq=False)
class ModelGain(GainSource):
    """A trained predictor in the loop.

    ``model`` is any object with a pure jitted
    ``apply(probs) -> (phi_hat, sigma)`` over float32 (S, C) local-
    classifier probabilities (:class:`~repro.gain.model.RidgeGainModel`,
    :class:`~repro.gain.model.SeqGainModel`); ``local_probs`` is the
    pool images' (S, C) local softmax output — the device-side signal
    the paper's predictor sees.  With ``quantize=True`` (default) the
    predicted phi table is snapped onto a ``sim.num_w_levels``-point
    uniform gain grid (the same granularity as the quantized state
    space), so the resolved table takes at most ``num_w_levels``
    distinct values and freezing via ``to_pool_tables()`` round-trips
    bit-identically through a ``TableGain``.
    """

    model: object
    local_probs: np.ndarray
    quantize: bool = True

    def tables(self, pool, sim) -> GainTables:
        probs = jnp.asarray(self.local_probs, jnp.float32)
        if probs.ndim != 2 or probs.shape[0] != len(pool.local_correct):
            raise ValueError(
                f"local_probs shape {probs.shape} does not cover the "
                f"pool's {len(pool.local_correct)} images")
        phi, sig = self.model.apply(probs)
        phi = jnp.clip(jnp.asarray(phi, jnp.float32), 0.0, 1.0)
        sig = jnp.maximum(jnp.asarray(sig, jnp.float32), 0.0)
        if self.quantize:
            hi = jnp.maximum(jnp.quantile(phi, 0.999), jnp.float32(0.1))
            phi = snap_to_grid(phi, sim.num_w_levels, hi)
        return GainTables(phi, sig)


def as_gain_source(source) -> GainSource:
    """Normalize a ``gain_source=`` argument: None -> TableGain, a
    string name -> the trivial sources, a GainSource passes through."""
    if source is None:
        return TableGain()
    if isinstance(source, GainSource):
        return source
    if isinstance(source, str):
        named = {"table": TableGain, "overlay": OverlayGain}
        if source in named:
            return named[source]()
        raise ValueError(f"unknown gain source {source!r}; named sources: "
                         f"{sorted(named)} (ModelGain needs a model)")
    raise TypeError(f"not a GainSource: {source!r}")
