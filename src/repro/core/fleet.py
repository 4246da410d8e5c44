"""Vectorized fleet simulation: run OnAlgo / baselines over a trace with scan.

``simulate`` rolls a (T, N) state-index trace through a policy, producing
per-slot series (reward, power, load, duals, diagnostics) and the final
algorithm state.  With a ``RawOverlay`` it is also the engine behind the
end-to-end service simulator (serve/compile.py lowers a SimConfig to the
``(Trace, tables, params, overlay)`` contract).  ``simulate_sharded``
wraps the same slot function in
``shard_map`` over the mesh ``data`` axis — devices are sharded, lambda is
shard-local, and the single mu/psum is the only cross-shard communication,
mirroring the paper's device<->cloudlet protocol.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import spans
from repro.core import baselines as bl
from repro.core import onalgo
from repro.core.onalgo import OnAlgoParams, StepRule
from repro.parallel.mesh import fleet_mesh
from repro.topology import Topology, validate_topology


def _topo_duals(topology: Optional[Topology]) -> Optional[Topology]:
    """The topology driving K-vector duals, or None when the scalar path
    applies (no topology, or K == 1 — one cloudlet's dual IS mu; the
    association is irrelevant and the rollout is bit-identical to the
    scalar engines, with per-slot admission under H_k[0])."""
    return topology if (topology is not None and topology.K > 1) else None


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Trace:
    """A fleet trace: per-slot per-device quantized state indices + extras.

    j_idx: (T, N) int32 state indices into the StateSpace tables (0 = null).
    d_local: (T, N) float32 local-classifier confidence (for ATO), or zeros.
    """

    j_idx: jax.Array
    d_local: jax.Array

    @property
    def T(self):
        return self.j_idx.shape[0]

    @property
    def N(self):
        return self.j_idx.shape[1]


def _lookup(tab, j):
    """Value lookup for (M,) shared or (N, M) per-device tables."""
    if tab.ndim == 1:
        return tab[j]
    return jax.vmap(lambda row, idx: row[idx])(tab, j)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RawOverlay:
    """Raw (unquantized) per-slot values riding alongside a quantized Trace.

    The service tier observes RAW values each slot — channel-dependent power,
    image-size cycles, predictor gains — and only the running distribution
    rho uses the quantized state index.  Compiling a service run
    (serve/compile.py) pre-samples these into (T, N) arrays so the fleet
    engine can reproduce the end-to-end simulator's accounting exactly:
    decisions and series use the raw values, rho uses ``trace.j_idx``.

    o / h / w: (T, N) float32 observed power (W), cloudlet cycles, and
      risk-adjusted predicted gain.  Where the gain comes from — pool
      tables, a pre-folded overlay, or a trained predictor — is the
      :mod:`repro.gain` tier's choice; by the time an overlay exists the
      source has already been resolved into these raw streams.
    correct_local / correct_cloud: (T, N) float32 — whether the local /
      cloudlet classifier got this slot's sampled image right (drives the
      service accuracy series).
    """

    o: jax.Array
    h: jax.Array
    w: jax.Array
    correct_local: jax.Array
    correct_cloud: jax.Array


@partial(jax.jit,
         static_argnames=("algo", "enforce_slot_capacity", "use_kernel",
                          "with_true_rho", "collect_decisions"))
def simulate(trace: Trace,
             tables,
             params: OnAlgoParams,
             rule: StepRule,
             algo: str = "onalgo",
             ato_theta: float = 0.5,
             enforce_slot_capacity: bool = False,
             use_kernel: bool = False,
             true_rho: Optional[jax.Array] = None,
             with_true_rho: bool = False,
             overlay: Optional[RawOverlay] = None,
             topology: Optional[Topology] = None,
             collect_decisions: bool = False):
    """Roll a trace through a policy.

    Returns (series dict of (T,) arrays, final_state).  Accounting:
      * power is spent on every transmission (offload), admitted or not;
      * accuracy gain w is realized only for admitted tasks;
      * with ``enforce_slot_capacity`` the cloudlet drops tasks beyond H per
        slot (the paper's comparison rule); OnAlgo itself needs no dropping
        asymptotically since it enforces the average constraint.
      * with ``with_true_rho`` (requires true_rho) the series include
        f(y_t)/g(y_t) evaluated under the TRUE distribution — the quantities
        bounded by Theorem 1.
      * with ``overlay`` (service tier) the per-slot values o/h/w come from
        the raw arrays instead of table lookups — exactly what a device
        observes — and the series gain ``correct``: per-slot count of tasks
        whose final classification (cloudlet if admitted, local otherwise)
        was right.
      * with ``topology`` (multi-cloudlet tier) the capacity dual is a
        (K,) vector: each device is priced by its current cloudlet's
        entry (``assoc``), the dual ascent runs per cloudlet on
        segment-reduced loads, and per-slot admission applies H_k per
        cloudlet.  The series gain ``mu_k`` (T, K); ``mu`` becomes the
        cloudlet mean.  K = 1 is the scalar path bit for bit.

    ``algo`` covers OnAlgo, the paper's three baselines, and the service
    tier's two degenerate policies: ``local`` (never offload) and ``cloud``
    (offload every task, cloudlet admission permitting).

    ``collect_decisions`` adds the realized per-device decision matrices
    to the series — ``offload_mask`` / ``admit_mask``, (T, N) bool —
    the ground truth the live gateway's replay is checked against, and
    for OnAlgo ``offload_margin`` (T, N) fp32, each decision's
    ``onalgo.decision_margin`` (O(T * N) memory: a test/diagnostics
    flag, not a fleet-scale one).
    """
    o_tab, h_tab, w_tab = tables
    T, N = trace.j_idx.shape
    M = o_tab.shape[-1]

    validate_topology(topology, T, N)
    topo_k = _topo_duals(topology)
    if topo_k is not None:
        # a time-varying map may cover MORE slots than this rollout
        # (mobility walks are horizon-extensible); the scan consumes
        # exactly T rows
        topo_k = topo_k.prefix(T)
        if use_kernel:
            raise ValueError(
                "use_kernel routes the scalar-mu single-slot kernel and "
                "does not support topology.K > 1; run with "
                "use_kernel=False or through the chunked engines")

    if algo == "onalgo":
        algo_state = onalgo.init_state(
            N, M, K=None if topo_k is None else topo_k.K)
    elif algo == "ato":
        algo_state = bl.ATOState(theta=jnp.float32(ato_theta))
    elif algo == "rco":
        algo_state = bl.RCOState(energy=jnp.zeros((N,), jnp.float32),
                                 t=jnp.zeros((), jnp.int32))
    elif algo in ("ocos", "local", "cloud"):
        algo_state = bl.OCOSState()
    else:
        raise ValueError(f"unknown algo {algo!r}")

    xs = {"j": trace.j_idx, "d": trace.d_local}
    if overlay is not None:
        xs.update(o=overlay.o, h=overlay.h, w=overlay.w,
                  cl=overlay.correct_local, cc=overlay.correct_cloud)
    if topo_k is not None and topo_k.time_varying:
        # materializes a streaming walk — the scan engine consumes the
        # horizon as scan xs anyway
        xs["assoc"] = topo_k.assoc_at(0, T)

    def slot(carry, xs):
        state = carry
        j, d_loc = xs["j"], xs["d"]
        if overlay is None:
            o_now = _lookup(o_tab, j)
            h_now = _lookup(h_tab, j)
            w_now = _lookup(w_tab, j)
        else:
            o_now, h_now, w_now = xs["o"], xs["h"], xs["w"]
            c_loc, c_cloud = xs["cl"], xs["cc"]
        task = j > 0
        assoc_now = None
        if topo_k is not None:
            assoc_now = (xs["assoc"] if topo_k.time_varying
                         else topo_k.assoc)

        mu_k = None
        if algo == "onalgo" and collect_decisions:
            margin = onalgo.decision_margin(
                state, o_now, h_now, w_now, params,
                assoc=None if topo_k is None else assoc_now)
        if algo == "onalgo":
            if topo_k is None:
                state, offload = onalgo.step(state, j, o_now, h_now, w_now,
                                             task, tables, params, rule,
                                             use_kernel=use_kernel)
                # ||(lambda, mu)|| — the full dual vector norm of Theorem 1.
                lam_norm = jnp.sqrt(jnp.sum(state.lam**2) + state.mu**2)
                mu = state.mu
            else:
                state, offload = onalgo.step(state, j, o_now, h_now, w_now,
                                             task, tables, params, rule,
                                             assoc=assoc_now,
                                             H_k=topo_k.H_k)
                lam_norm = jnp.sqrt(jnp.sum(state.lam**2)
                                    + jnp.sum(state.mu**2))
                mu_k = state.mu
                mu = jnp.mean(mu_k)
        elif algo == "ato":
            state, offload = bl.ato_step(state, d_loc, o_now, task)
            lam_norm = jnp.float32(0.0)
            mu = jnp.float32(0.0)
        elif algo == "rco":
            state, offload = bl.rco_step(state, o_now, params.B, task)
            lam_norm = jnp.float32(0.0)
            mu = jnp.float32(0.0)
        elif algo == "local":
            offload = jnp.zeros_like(task)
            lam_norm = jnp.float32(0.0)
            mu = jnp.float32(0.0)
        else:  # ocos / cloud: offload every task
            state, offload = bl.ocos_step(state, task)
            lam_norm = jnp.float32(0.0)
            mu = jnp.float32(0.0)

        if enforce_slot_capacity:
            if topology is None:
                admitted = bl.admit_by_capacity(
                    offload, h_now, params.H,
                    smallest_first=(algo == "ocos"))
            else:
                admitted = bl.admit_by_capacity_topo(
                    offload, h_now, assoc_now, topology.H_k,
                    smallest_first=(algo == "ocos"))
        else:
            admitted = offload

        offload_f = offload.astype(jnp.float32)
        admit_f = admitted.astype(jnp.float32)
        out = {
            "reward": jnp.sum(w_now * admit_f),
            "power": jnp.sum(o_now * offload_f),
            "power_per_dev": jnp.mean(o_now * offload_f),
            "load": jnp.sum(h_now * admit_f),
            "offloads": jnp.sum(offload_f),
            "admits": jnp.sum(admit_f),
            "tasks": jnp.sum(task.astype(jnp.float32)),
            "lam_norm": lam_norm,
            "mu": mu,
        }
        if collect_decisions:
            out["offload_mask"] = offload
            out["admit_mask"] = admitted
            if algo == "onalgo":
                out["offload_margin"] = margin
        if topology is not None:
            out["mu_k"] = (mu_k if mu_k is not None
                           else jnp.full((topology.K,), mu))
        if overlay is not None:
            # final classification: cloudlet result if admitted, local else
            out["correct"] = jnp.sum(
                jnp.where(admitted, c_cloud, c_loc)
                * task.astype(jnp.float32))
        if with_true_rho:
            # All Theorem-1 quantities live in the (optionally) preconditioned
            # constraint space — the space the duals are updated in.
            o_s, h_s, B_eff, H_eff = onalgo.precondition_tables(
                o_tab, h_tab, params)
            o_s = jnp.broadcast_to(o_s, (N, M))
            h_s = jnp.broadcast_to(h_s, (N, M))
            if algo == "onalgo":
                lam_, mu_ = state.lam, state.mu
                rho_t = state.rho.rho
            else:
                lam_ = jnp.zeros((N,), jnp.float32)
                mu_ = (jnp.float32(0.0) if topo_k is None
                       else jnp.zeros((topo_k.K,), jnp.float32))
                rho_t = true_rho
            y_pol = onalgo.policy_matrix(
                lam_, mu_, o_s, h_s, w_tab,
                assoc=None if topo_k is None else assoc_now)
            w_full = jnp.broadcast_to(w_tab, (N, M))
            # f/g of the slot policy under the TRUE distribution — the
            # quantities Theorem 1 bounds (reward convention: higher better).
            out["f_true"] = jnp.sum(w_full * true_rho * y_pol)
            g_pow = jnp.sum(o_s * true_rho * y_pol, axis=-1) - B_eff
            # Perturbation terms delta_t(y_t) (Sec. IV.C.2): the rho_t - rho
            # error projected on the policy, per constraint row.
            drho = rho_t - true_rho
            d_pow = jnp.sum(o_s * drho * y_pol, axis=-1)  # (N,)
            if topo_k is None:
                g_cap = jnp.sum(h_s * true_rho * y_pol) - H_eff
                d_cap = jnp.sum(h_s * drho * y_pol)  # ()
            else:
                # K capacity rows: per-cloudlet loads of the policy under
                # the true distribution, in the same (preconditioned)
                # space the K-vector dual ascends in.
                H_k_eff = (topo_k.H_k / params.H if params.precondition
                           else topo_k.H_k)
                g_cap = onalgo.capacity_loads(
                    y_pol, true_rho, h_s, assoc_now, topo_k.K) - H_k_eff
                d_cap = onalgo.capacity_loads(
                    y_pol, drho, h_s, assoc_now, topo_k.K)  # (K,)
            out["g_pow"] = g_pow
            out["g_cap"] = g_cap
            out["delta_norm"] = jnp.sqrt(jnp.sum(d_pow**2)
                                         + jnp.sum(d_cap**2))
            out["lam_delta"] = jnp.sum(lam_ * d_pow) + jnp.sum(mu_ * d_cap)
        return state, out

    final_state, series = jax.lax.scan(slot, algo_state, xs)
    return series, final_state


def _series_from_offloads(j_seq, off, tables, params, mu_seq, lnorm,
                          overlay: Optional[RawOverlay],
                          enforce_slot_capacity: bool,
                          smallest_first: bool = False,
                          topology: Optional[Topology] = None,
                          t0: int = 0, collect_decisions: bool = False):
    """Whole-horizon series assembly shared by the offload-matrix engines.

    The chunked/tiled kernels and the sharded scan produce the realized
    (T, N) offload matrix plus the dual series; everything else in the
    ``simulate`` series contract is a pure function of that matrix — the
    per-slot cloudlet admission post-pass and the o/h/w accounting (table
    lookups, or the raw overlay streams plus the ``correct`` series for
    the service tier).  Centralizing it here keeps every engine's
    accounting bit-identical.

    ``topology`` switches admission per-cloudlet (H_k under the ``assoc``
    ids — ``t0`` locates this span inside a time-varying map) and adds
    the ``mu_k`` series; ``mu_seq`` may then be (T, K) per-cloudlet duals
    (the scalar ``mu`` series becomes their cloudlet mean).
    ``collect_decisions`` adds the ``offload_mask`` / ``admit_mask``
    matrices, as in ``simulate``.
    """
    with jax.named_scope(spans.ACCOUNTING):
        o_tab, h_tab, w_tab = tables
        if overlay is None:
            lookup_t = jax.vmap(_lookup, in_axes=(None, 0))
            o_seq = lookup_t(o_tab, j_seq)  # (T, N)
            h_seq = lookup_t(h_tab, j_seq)
            w_seq = lookup_t(w_tab, j_seq)
        else:
            o_seq, h_seq, w_seq = overlay.o, overlay.h, overlay.w
        off_f = off.astype(jnp.float32)
        if enforce_slot_capacity:
            with jax.named_scope(spans.ADMISSION):
                if topology is None:
                    admit = partial(bl.admit_by_capacity, H_slot=params.H,
                                    smallest_first=smallest_first)
                    admitted = jax.vmap(admit)(off, h_seq)
                else:
                    admit = partial(bl.admit_by_capacity_topo,
                                    H_k=topology.H_k,
                                    smallest_first=smallest_first)
                    if topology.K == 1:  # assoc is irrelevant, one cloudlet
                        admitted = jax.vmap(
                            lambda o_, h_: admit(o_, h_, None))(off, h_seq)
                    else:
                        # one slot at a time: batched over slots, the
                        # argsort and the scatter back compile for
                        # minutes at fleet scale on the TPU; one slot's
                        # program compiles in seconds
                        a_seq = topology.assoc_at(t0, off.shape[0])
                        admitted = jax.lax.map(lambda x: admit(*x),
                                               (off, h_seq, a_seq))
        else:
            admitted = off
        adm_f = admitted.astype(jnp.float32)
        task_f = (j_seq > 0).astype(jnp.float32)
        series = {
            "reward": jnp.sum(w_seq * adm_f, axis=1),
            "power": jnp.sum(o_seq * off_f, axis=1),
            "power_per_dev": jnp.mean(o_seq * off_f, axis=1),
            "load": jnp.sum(h_seq * adm_f, axis=1),
            "offloads": jnp.sum(off_f, axis=1),
            "admits": jnp.sum(adm_f, axis=1),
            "tasks": jnp.sum(task_f, axis=1),
            "lam_norm": lnorm,
        }
        if mu_seq.ndim == 2:  # (T, K) per-cloudlet duals
            series["mu_k"] = mu_seq
            series["mu"] = jnp.mean(mu_seq, axis=-1)
        else:
            series["mu"] = mu_seq
            if topology is not None:
                series["mu_k"] = jnp.broadcast_to(
                    mu_seq[:, None], (mu_seq.shape[0], topology.K))
        if overlay is not None:
            series["correct"] = jnp.sum(
                jnp.where(admitted, overlay.correct_cloud,
                          overlay.correct_local) * task_f, axis=1)
        if collect_decisions:
            series["offload_mask"] = off
            series["admit_mask"] = admitted
        return series


def _trivial_policy_rollout(j_seq, algo: str):
    """Offload matrix + (zero) dual series for the stateless policies."""
    task = j_seq > 0
    off = task if algo == "cloud" else jnp.zeros_like(task)
    T = j_seq.shape[0]
    zeros = jnp.zeros((T,), jnp.float32)
    return off, zeros, zeros, bl.OCOSState()


def _overlay_slot_values(overlay: RawOverlay, params: OnAlgoParams):
    """The overlay's raw decision streams, mapped to the dual space the
    kernels operate in (same diagonal preconditioner as onalgo.step)."""
    if not params.precondition:
        return (overlay.o, overlay.h, overlay.w)
    return (overlay.o / params.B[None, :], overlay.h / params.H, overlay.w)


def _onalgo_tail(state, j_tail, overlay_tail: Optional[RawOverlay],
                 tables, params: OnAlgoParams, rule: StepRule,
                 topo_k: Optional[Topology] = None,
                 assoc_tail: Optional[jax.Array] = None):
    """Finish a sub-chunk tail with the jnp slot step.

    Shared by the materialized and streaming chunked engines so the two
    tails cannot drift.  ``topo_k`` (a K > 1 topology) switches the step
    to the K-vector duals; ``assoc_tail`` is its (Lt, N) association
    slab (None for a static map).  Returns (state, off (Lt, N) bool,
    mu_seq (Lt,) or (Lt, K), lam_norm (Lt,)).
    """
    o_tab, h_tab, w_tab = tables

    def slot(state, xs):
        j = xs["j"]
        if overlay_tail is None:
            o_now = _lookup(o_tab, j)
            h_now = _lookup(h_tab, j)
            w_now = _lookup(w_tab, j)
        else:  # raw (unpreconditioned) values; step rescales them
            o_now, h_now, w_now = xs["o"], xs["h"], xs["w"]
        task = j > 0
        if topo_k is None:
            state, offload = onalgo.step(state, j, o_now, h_now, w_now,
                                         task, tables, params, rule)
            lam_norm = jnp.sqrt(jnp.sum(state.lam**2) + state.mu**2)
        else:
            assoc_now = (xs["assoc"] if topo_k.time_varying
                         else topo_k.assoc)
            state, offload = onalgo.step(state, j, o_now, h_now, w_now,
                                         task, tables, params, rule,
                                         assoc=assoc_now, H_k=topo_k.H_k)
            lam_norm = jnp.sqrt(jnp.sum(state.lam**2)
                                + jnp.sum(state.mu**2))
        return state, (offload, state.mu, lam_norm)

    xs_tail = {"j": j_tail}
    if overlay_tail is not None:
        xs_tail.update(o=overlay_tail.o, h=overlay_tail.h,
                       w=overlay_tail.w)
    if topo_k is not None and topo_k.time_varying:
        xs_tail["assoc"] = assoc_tail
    state, (off_t, mu_t, ln_t) = jax.lax.scan(slot, state, xs_tail)
    return state, off_t, mu_t, ln_t


def _rollout_kernel(N: int, M: int, block_n: Optional[int]):
    """The fused rollout kernel for N devices over M states: the
    device-tiled kernel with ``block_n`` devices per tile when given,
    else whichever of whole-fleet / tiled the fleet's size calls for
    (``kernels.onalgo_step.rollout_block_n``)."""
    from repro.kernels import ops as kops
    from repro.kernels.onalgo_step import rollout_block_n

    if block_n is None:
        block_n = rollout_block_n(N, M)
    return (kops.onalgo_chunked if block_n is None
            else partial(kops.onalgo_tiled, block_n=block_n))


@partial(jax.jit, static_argnames=("chunk", "block_n", "algo",
                                   "enforce_slot_capacity",
                                   "collect_decisions"))
def simulate_chunked(trace: Trace, tables, params: OnAlgoParams,
                     rule: StepRule, chunk: int = 8,
                     block_n: Optional[int] = None,
                     algo: str = "onalgo",
                     overlay: Optional[RawOverlay] = None,
                     enforce_slot_capacity: bool = False,
                     topology: Optional[Topology] = None,
                     topo_binned: Optional[bool] = None,
                     collect_decisions: bool = False,
                     t0=0, state0: Optional[onalgo.OnAlgoState] = None):
    """OnAlgo rollout through the fused whole-simulation Pallas kernels.

    Equivalent to ``simulate(..., algo="onalgo")`` (same series keys, same
    final state) but the whole horizon runs as ONE fused kernel: ``chunk``
    slots of rho-update + threshold policy + dual ascent per grid step
    (see kernels/onalgo_step.py).  A non-divisible tail of ``T mod chunk``
    slots is finished by the jnp slot step.

    block_n: None (default) picks the kernel from N and M — the whole
      fleet's tables/state VMEM-resident while they fit, else the
      device-tiled kernel (O(block_n * M) VMEM, any fleet size); an int
      forces the tiled kernel with block_n devices per tile.
    algo: ``onalgo`` (the kernels), or the service tier's stateless
      ``local`` / ``cloud`` policies (no kernel needed).
    overlay: optional service-tier RawOverlay — raw per-slot values drive
      the realized decision and the accounting (and the series gain
      ``correct``), while rho and the duals stay on the quantized tables,
      exactly like ``simulate(..., overlay=...)``.
    enforce_slot_capacity: apply the paper's per-slot cloudlet admission
      rule as a vmapped post-pass over the offload matrix, so reward / load
      / admits match ``simulate(..., enforce_slot_capacity=True)``.  The
      dual dynamics are untouched (they live on the average constraint).
    topology: multi-cloudlet tier — the kernels carry the (K,) capacity
      duals in a VMEM-resident row, price each device by its current
      cloudlet's entry (assoc columns ride the trace layout), and reduce
      per-cloudlet loads in-kernel; admission runs per cloudlet.  K = 1
      takes the scalar kernels bit for bit.
    topo_binned: route the in-kernel per-cloudlet reductions through the
      binned (hi, lo) = (k // 128, k % 128) layout — O(K / 128) mask
      memory and an MXU contraction instead of an (N, K_pad) one-hot
      mask.  None (default) auto-selects by K; ``fleet.autotune`` probes
      both on large-K topologies.  Ignored without a topology.
    collect_decisions: add the realized ``offload_mask`` / ``admit_mask``
      (T, N) matrices to the series, as ``simulate`` does.
    t0 / state0: resume mid-horizon — the trace (and overlay, topology)
      cover slots [t0, t0 + T), rolled from ``state0`` (an OnAlgoState
      whose ``rho.t`` is t0; ``t0`` may be traced).  Bit-identical to the
      same span of one run from slot 0.
    """
    o_tab, h_tab, w_tab = tables
    T, N = trace.j_idx.shape
    M = o_tab.shape[-1]
    j_seq = trace.j_idx
    validate_topology(topology, T, N)
    topo_k = _topo_duals(topology)

    if algo in ("local", "cloud"):
        off, mu_seq, lnorm, final = _trivial_policy_rollout(j_seq, algo)
        series = _series_from_offloads(j_seq, off, tables, params, mu_seq,
                                       lnorm, overlay,
                                       enforce_slot_capacity,
                                       topology=topology,
                                       collect_decisions=collect_decisions)
        return series, final
    if algo != "onalgo":
        raise ValueError("the chunked engine rolls OnAlgo (plus the "
                         f"stateless local/cloud policies); got {algo!r}")

    o_s, h_s, B_eff, H_eff = onalgo.precondition_tables(o_tab, h_tab,
                                                        params)
    slot_values = (None if overlay is None
                   else _overlay_slot_values(overlay, params))
    topo_kw = {}
    if topo_k is not None:
        H_k_eff = (topo_k.H_k / params.H if params.precondition
                   else topo_k.H_k)
        topo_kw = dict(H_k=H_k_eff, topo_binned=topo_binned)

    T_main = (T // chunk) * chunk
    if state0 is None:
        state0 = onalgo.init_state(
            N, M, K=None if topo_k is None else topo_k.K)
    lam, mu, counts = state0.lam, state0.mu, state0.rho.counts
    if T_main:
        kern = _rollout_kernel(N, M, block_n)
        sv_main = (None if slot_values is None
                   else tuple(sv[:T_main] for sv in slot_values))
        if topo_k is not None:  # static maps stay (N,): no (T, N) bcast
            topo_kw["assoc"] = (topo_k.assoc_at(0, T_main)
                                if topo_k.time_varying else topo_k.assoc)
        off, mu_seq, lnorm, lam, mu, counts = kern(
            j_seq[:T_main], lam, mu, counts, o_s, h_s, w_tab, B_eff, H_eff,
            rule.a, rule.beta, chunk=chunk, t0=t0, slot_values=sv_main,
            **topo_kw)
    else:  # whole horizon shorter than one chunk: jnp tail does it all
        off = jnp.zeros((0, N), bool)
        mu_seq = jnp.zeros((0,) if topo_k is None else (0, topo_k.K),
                           jnp.float32)
        lnorm = jnp.zeros((0,), jnp.float32)

    if T_main < T:  # finish the tail with the jnp slot step
        state = onalgo.OnAlgoState(
            lam=lam, mu=mu,
            rho=onalgo.RhoEstimator(counts=counts,
                                    t=jnp.int32(t0 + T_main)))
        overlay_tail = None if overlay is None else RawOverlay(
            o=overlay.o[T_main:], h=overlay.h[T_main:],
            w=overlay.w[T_main:],
            correct_local=overlay.correct_local[T_main:],
            correct_cloud=overlay.correct_cloud[T_main:])
        assoc_tail = (topo_k.assoc_at(T_main, T - T_main)
                      if topo_k is not None and topo_k.time_varying
                      else None)
        state, off_t, mu_t, ln_t = _onalgo_tail(
            state, j_seq[T_main:], overlay_tail, tables, params, rule,
            topo_k=topo_k, assoc_tail=assoc_tail)
        off = jnp.concatenate([off, off_t], axis=0)
        mu_seq = jnp.concatenate([mu_seq, mu_t])
        lnorm = jnp.concatenate([lnorm, ln_t])
        lam, mu, counts = state.lam, state.mu, state.rho.counts

    series = _series_from_offloads(j_seq, off, tables, params, mu_seq,
                                   lnorm, overlay, enforce_slot_capacity,
                                   topology=topology,
                                   collect_decisions=collect_decisions)
    final = onalgo.OnAlgoState(
        lam=lam, mu=mu,
        rho=onalgo.RhoEstimator(counts=counts, t=jnp.int32(t0 + T)))
    return series, final


def _cat_series(parts):
    """Concatenate per-slab series dicts along the time axis."""
    return {k: jnp.concatenate([p[k] for p in parts]) for k in parts[0]}


# The pipelined streaming runtime turns on automatically at fleet sizes
# where the per-slab host round-trip (one jit call for generation, one
# for the kernel, ~10 eager accounting dispatches, a Python list append)
# costs more than the one-off trace+compile of the fused slab step.
_PIPELINE_AUTO_N = 65536


class _StaticSource:
    """Identity-hashed wrapper making any slab source a valid jit static.

    The fused slab step closes over nothing: the source callable enters
    ``_pipelined_slab_step`` as a STATIC argument so every slab of a run
    — and every later run over the same source object — reuses one
    compiled executable.  Bound methods are re-created on each attribute
    access (``svc.slab is svc.slab`` is False) and may hang off
    unhashable instances, so the cache key is ``(__func__,
    id(__self__))``; the jit cache keeps the wrapper (hence the bound
    instance) alive, so the id cannot be recycled while the entry lives.
    """

    __slots__ = ("fn", "_key")

    def __init__(self, fn):
        self.fn = fn
        bound = getattr(fn, "__self__", None)
        self._key = ((fn.__func__, id(bound)) if bound is not None
                     else (fn, None))

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return (isinstance(other, _StaticSource)
                and self._key == other._key)

    def __call__(self, t0, length):
        return self.fn(t0, length)


def _stream_series_buffers(length: int, topology: Optional[Topology],
                           has_overlay: bool) -> dict:
    """Preallocated device-resident series buffers for a streaming run.

    One (length,) float32 buffer per series key (``mu_k`` is
    (length, K)); the fused slab steps write each slab's accounting into
    them with ``dynamic_update_slice`` so no per-slab part ever reaches
    the host — the whole dict transfers once, at the end of the run.
    Key set mirrors :func:`_series_from_offloads` exactly (a mismatch
    fails loudly at trace time in the slab step's update).
    """
    keys = ["reward", "power", "power_per_dev", "load", "offloads",
            "admits", "tasks", "lam_norm", "mu"]
    bufs = {k: jnp.zeros((length,), jnp.float32) for k in keys}
    if topology is not None:
        bufs["mu_k"] = jnp.zeros((length, topology.K), jnp.float32)
    if has_overlay:
        bufs["correct"] = jnp.zeros((length,), jnp.float32)
    return bufs


def _write_series(bufs: dict, part: dict, at) -> dict:
    """Write one slab's series ``part`` into the run buffers at ``at``
    (traced offset).  Works traced (inside the fused step) and eager
    (folding the jnp tail after the loop)."""
    with jax.named_scope(spans.ACCOUNTING):
        return {k: jax.lax.dynamic_update_slice_in_dim(
            bufs[k], part[k].astype(bufs[k].dtype), at, axis=0)
            for k in bufs}


@partial(jax.jit, donate_argnums=(0,), static_argnames=("enforce",))
def _stream_acct(bufs, off, j_slab, overlay, mu_seq, lnorm, t0, tables,
                 params, topology, *, enforce: bool):
    """The device-resident accounting half of a pipelined SHARDED walk.

    The shard_map rollout stays its own launch — fusing a jnp scan into
    a larger jit lets XLA re-associate its arithmetic (the lam-norm
    sqrt picks up an FMA), which would break the bit-identity contract
    with the sequential walk — so only the accounting post-pass and the
    series-buffer writes ride this donated-carry dispatch.  (The
    chunked engine has no such hazard: its rollout is an opaque Pallas
    call XLA cannot fuse into, so :func:`_pipelined_slab_step` fuses
    generation + rollout + accounting into one launch.)
    """
    part = _series_from_offloads(j_slab, off, tables, params, mu_seq,
                                 lnorm, overlay, enforce,
                                 topology=topology, t0=t0)
    return _write_series(bufs, part, t0)


@partial(jax.jit,
         static_argnames=("src", "L", "chunk", "block_n",
                          "enforce_slot_capacity", "topo_binned"),
         donate_argnums=(0,))
def _pipelined_slab_step(carry, t0, t_buf, tables, params, rule, topology,
                         *, src, L, chunk, block_n,
                         enforce_slot_capacity, topo_binned):
    """One fused launch of the pipelined chunked stream: slab generation
    (+ assoc slab + overlay gathers), the Pallas rollout, and the
    device-resident accounting, in a single jitted call.

    The carried ``(lam, mu, counts, series_buffers)`` tuple is DONATED:
    shapes are loop-invariant, so steady state reuses the same device
    buffers launch after launch and allocates nothing.  ``t0`` (global
    slot) and ``t_buf`` (buffer write offset, differs when resuming from
    t0 > 0) are traced — every slab of a run shares this one compile.
    The host loop never touches the outputs, so slab t+1's launch is
    enqueued while slab t is still executing (double-buffered dispatch).
    """
    lam, mu, counts, bufs = carry
    j_slab, overlay = src(t0, L)
    o_tab, h_tab, w_tab = tables
    with jax.named_scope(spans.ROLLOUT):  # the kernel's operands
        o_s, h_s, B_eff, H_eff = onalgo.precondition_tables(o_tab, h_tab,
                                                            params)
        sv = (None if overlay is None
              else _overlay_slot_values(overlay, params))
    topo_k = _topo_duals(topology)
    topo_kw = {}
    if topo_k is not None:
        H_k_eff = (topo_k.H_k / params.H if params.precondition
                   else topo_k.H_k)
        topo_kw = dict(assoc=(topo_k.assoc_at(t0, L)
                              if topo_k.time_varying else topo_k.assoc),
                       H_k=H_k_eff, topo_binned=topo_binned)
    kern = _rollout_kernel(j_slab.shape[1], counts.shape[-1], block_n)
    off, mu_seq, lnorm, lam, mu, counts = kern(
        j_slab, lam, mu, counts, o_s, h_s, w_tab, B_eff, H_eff,
        rule.a, rule.beta, chunk=chunk, t0=t0, slot_values=sv, **topo_kw)
    part = _series_from_offloads(j_slab, off, tables, params, mu_seq,
                                 lnorm, overlay, enforce_slot_capacity,
                                 topology=topology, t0=t0)
    return lam, mu, counts, _write_series(bufs, part, t_buf)


def _stream_trivial(source, T: int, N: int, slab: int, tables,
                    params: OnAlgoParams, algo: str,
                    enforce_slot_capacity: bool,
                    topology: Optional[Topology] = None, start: int = 0):
    """local / cloud policies over a streamed workload: stateless, so the
    rollout is just per-slab accounting."""
    parts = []
    for t0 in range(start, T, slab):
        L = min(slab, T - t0)
        j_slab, overlay = source(t0, L)
        off, mu_seq, lnorm, final = _trivial_policy_rollout(j_slab, algo)
        parts.append(_series_from_offloads(j_slab, off, tables, params,
                                           mu_seq, lnorm, overlay,
                                           enforce_slot_capacity,
                                           topology=topology, t0=t0))
    return _cat_series(parts), final


def simulate_chunked_stream(source, T: int, N: int, tables,
                            params: OnAlgoParams, rule: StepRule, *,
                            chunk: int = 16, slab: Optional[int] = None,
                            block_n: Optional[int] = None,
                            algo: str = "onalgo",
                            enforce_slot_capacity: bool = False,
                            topology: Optional[Topology] = None,
                            topo_binned: Optional[bool] = None,
                            pipelined: Optional[bool] = None,
                            source_aligned=None, t0: int = 0,
                            state0=None):
    """The chunked engine over a *streamed* workload: no (T, N) horizon.

    ``source(t0, length)`` yields slots [t0, t0 + length) of the
    workload as ``(j_slab (L, N) int32, overlay: RawOverlay | None)`` —
    e.g. a jitted closure over a
    :class:`~repro.workload.streaming.StreamingWorkload` lowering.  The
    rollout walks the horizon ``slab`` slots at a time: generate the
    slab on device, run the fused Pallas kernel on it (resuming via its
    traced ``t0`` — one compile for every slab), fold the slab's
    accounting, drop the slab.  Peak device memory is O(slab * N) +
    O(N * M) state (streamed through O(block_n * M) VMEM tiles at
    fleet scale),
    independent of T * N; only the O(T) per-slot series survive.

    Metrics are identical to materializing the workload and calling
    ``simulate_chunked`` with the same ``chunk`` — the kernel calls see
    the same fp32 state and the same slab values (counter-addressed
    draws are slab-invariant), so the rollout is bit-equal.

    ``pipelined`` selects the PIPELINED runtime (default: automatic at
    N >= 65536): slab generation, the kernel, and the accounting fuse
    into ONE jitted launch per slab (:func:`_pipelined_slab_step`) with
    the carried duals/rho/series buffers donated, per-slab series
    written device-resident via ``dynamic_update_slice``, and no host
    sync inside the loop, so slab t+1 is enqueued while slab t executes.
    Results are bit-identical to the sequential walk (property-tested);
    the trade is one fused compile per distinct (source, slab length).

    ``source_aligned``, when given, is a source producing the same slabs
    from fewer covering ROW_BLOCK blocks when ``t0`` is ROW_BLOCK-
    aligned (e.g. ``StreamingService.slab_aligned``); the pipelined
    runtime uses it for the main slabs whenever the (start, slab) pair
    keeps every launch aligned.

    ``t0`` / ``state0`` resume the rollout mid-horizon: slots
    [t0, T) are rolled starting from ``state0`` (an ``OnAlgoState``
    whose ``rho.t`` must equal ``t0``) and the returned series covers
    exactly those T - t0 slots.  Bit-identical to the same span of a
    full run — slab and chunk boundaries are unobservable.

    Returns the standard ``(series, final_state)`` contract.
    """
    span = jax.profiler.TraceAnnotation
    with span(spans.STREAM_PREPARE):
        o_tab, h_tab, w_tab = tables
        M = o_tab.shape[-1]
        if slab is None:
            slab = chunk * 16
        if slab % chunk:
            raise ValueError(f"slab={slab} must be a multiple of "
                             f"chunk={chunk}")
        validate_topology(topology, T, N)
        topo_k = _topo_duals(topology)
        start = int(t0)
        if not 0 <= start < max(T, 1):
            raise ValueError(f"resume t0={start} outside horizon [0, {T})")
        if pipelined is None:
            pipelined = N >= _PIPELINE_AUTO_N
        stateless = algo in ("local", "cloud")
        if not stateless and algo != "onalgo":
            raise ValueError("the chunked streaming engine rolls OnAlgo "
                             "(plus the stateless local/cloud policies); "
                             f"got {algo!r}")
        if not stateless:
            lam, mu, counts = _stream_state0(state0, N, M, topo_k)
            T_main = start + ((T - start) // chunk) * chunk
            if pipelined:
                from repro.workload.streams import ROW_BLOCK
                use_aligned = (source_aligned is not None
                               and start % ROW_BLOCK == 0
                               and slab % ROW_BLOCK == 0)
                src = _StaticSource(source_aligned if use_aligned
                                    else source)
                probe_L = min(slab, T - start)
                has_overlay = jax.eval_shape(
                    lambda t: source(t, probe_L),
                    jax.ShapeDtypeStruct((), jnp.int32))[1] is not None
                bufs = _stream_series_buffers(T - start, topology,
                                              has_overlay)
            else:
                o_s, h_s, B_eff, H_eff = onalgo.precondition_tables(
                    o_tab, h_tab, params)
                kern = _rollout_kernel(N, M, block_n)
                if topo_k is not None:
                    H_k_eff = (topo_k.H_k / params.H if params.precondition
                               else topo_k.H_k)
    if stateless:
        return _stream_trivial(source, T, N, slab, tables, params, algo,
                               enforce_slot_capacity, topology=topology,
                               start=start)

    if pipelined:
        carry = (lam, mu, counts, bufs)
        for s0 in range(start, T_main, slab):
            L = min(slab, T_main - s0)
            with span(spans.STREAM_DISPATCH, t0=s0):
                carry = _pipelined_slab_step(
                    carry, jnp.int32(s0), jnp.int32(s0 - start), tables,
                    params, rule, topology, src=src, L=L, chunk=chunk,
                    block_n=block_n,
                    enforce_slot_capacity=enforce_slot_capacity,
                    topo_binned=topo_binned)
        lam, mu, counts, bufs = carry
        if T_main < T:  # finish the tail with the jnp slot step
            with span(spans.STREAM_TAIL):
                state, part = _stream_tail(
                    source, T_main, T, lam, mu, counts, tables, params,
                    rule, topology, enforce_slot_capacity)
                bufs = _write_series(bufs, part, T_main - start)
            lam, mu, counts = state.lam, state.mu, state.rho.counts
        final = onalgo.OnAlgoState(
            lam=lam, mu=mu,
            rho=onalgo.RhoEstimator(counts=counts, t=jnp.int32(T)))
        return bufs, final

    parts = []
    for s0 in range(start, T_main, slab):
        L = min(slab, T_main - s0)
        with span(spans.STREAM_DISPATCH, t0=s0):
            j_slab, overlay = source(s0, L)
            sv = (None if overlay is None
                  else _overlay_slot_values(overlay, params))
            topo_kw = ({} if topo_k is None
                       else dict(assoc=(topo_k.assoc_at(s0, L)
                                        if topo_k.time_varying
                                        else topo_k.assoc), H_k=H_k_eff,
                                 topo_binned=topo_binned))
            off, mu_seq, lnorm, lam, mu, counts = kern(
                j_slab, lam, mu, counts, o_s, h_s, w_tab, B_eff, H_eff,
                rule.a, rule.beta, chunk=chunk, t0=jnp.int32(s0),
                slot_values=sv, **topo_kw)
            parts.append(_series_from_offloads(
                j_slab, off, tables, params, mu_seq, lnorm, overlay,
                enforce_slot_capacity, topology=topology, t0=s0))
    if T_main < T:  # finish the tail with the jnp slot step
        with span(spans.STREAM_TAIL):
            state, part = _stream_tail(source, T_main, T, lam, mu, counts,
                                       tables, params, rule, topology,
                                       enforce_slot_capacity)
        parts.append(part)
        lam, mu, counts = state.lam, state.mu, state.rho.counts
    final = onalgo.OnAlgoState(
        lam=lam, mu=mu,
        rho=onalgo.RhoEstimator(counts=counts, t=jnp.int32(T)))
    return _cat_series(parts), final


def _stream_state0(state0, N: int, M: int, topo_k):
    """The (lam, mu, counts) a streaming walk starts from: copies of
    ``state0`` (the pipelined steps donate their carry, and the caller
    keeps its resume state), or the zero state."""
    if state0 is not None:
        return (jnp.array(state0.lam, jnp.float32),
                jnp.array(state0.mu, jnp.float32),
                jnp.array(state0.rho.counts, jnp.float32))
    mu = (jnp.float32(0.0) if topo_k is None
          else jnp.zeros((topo_k.K,), jnp.float32))
    return (jnp.zeros((N,), jnp.float32), mu,
            jnp.zeros((N, M), jnp.float32))


def _stream_tail(source, T_main: int, T: int, lam, mu, counts, tables,
                 params, rule, topology, enforce_slot_capacity):
    """Slots [T_main, T) of a streaming walk, shorter than a chunk, with
    the jnp slot step.  Returns (final state, the slots' series)."""
    topo_k = _topo_duals(topology)
    j_tail, overlay_t = source(T_main, T - T_main)
    state = onalgo.OnAlgoState(
        lam=lam, mu=mu,
        rho=onalgo.RhoEstimator(counts=counts, t=jnp.int32(T_main)))
    assoc_tail = (topo_k.assoc_at(T_main, T - T_main)
                  if topo_k is not None and topo_k.time_varying else None)
    state, off_t, mu_t, ln_t = _onalgo_tail(
        state, j_tail, overlay_t, tables, params, rule, topo_k=topo_k,
        assoc_tail=assoc_tail)
    part = _series_from_offloads(j_tail, off_t, tables, params, mu_t, ln_t,
                                 overlay_t, enforce_slot_capacity,
                                 topology=topology, t0=T_main)
    return state, part


def simulate_sharded(trace: Trace, tables, params: OnAlgoParams,
                     rule: StepRule, mesh, device_axis: str = "data",
                     algo: str = "onalgo",
                     overlay: Optional[RawOverlay] = None,
                     enforce_slot_capacity: bool = False,
                     topology: Optional[Topology] = None):
    """Distributed OnAlgo over a fleet sharded on a mesh axis.

    Devices (the N axis) are split across ``device_axis`` shards; each shard
    runs the device-local threshold rule and lambda updates; the cloudlet
    capacity sum is a psum — one scalar collective per slot, exactly the
    paper's protocol cost.  With a multi-cloudlet ``topology`` the psum
    carries the (K,) segment partials instead: each shard segment-reduces
    its own devices' loads by cloudlet id, so the association may cross
    shard boundaries freely and the per-slot collective stays one
    K-vector.

    Same ``(series, final_state)`` contract as ``simulate`` /
    ``simulate_chunked``: the sharded scan produces the realized offload
    matrix and the dual series; the accounting (including the optional
    per-slot admission post-pass and the overlay's ``correct`` series) is
    assembled globally from the gathered matrix, so the three engines'
    metrics agree.  ``algo`` covers ``onalgo`` plus the stateless
    ``local`` / ``cloud`` service policies.
    """
    o_tab, h_tab, w_tab = tables
    N = trace.N
    T = trace.T
    M = o_tab.shape[-1]
    validate_topology(topology, T, N)
    topo_k = _topo_duals(topology)
    if topo_k is not None:
        topo_k = topo_k.prefix(T)  # the sharded scan consumes T rows

    if algo in ("local", "cloud"):  # stateless: nothing to distribute
        off, mu_seq, lnorm, final = _trivial_policy_rollout(trace.j_idx,
                                                            algo)
        series = _series_from_offloads(trace.j_idx, off, tables, params,
                                       mu_seq, lnorm, overlay,
                                       enforce_slot_capacity,
                                       topology=topology)
        return series, final
    if algo != "onalgo":
        raise ValueError("the sharded engine rolls OnAlgo (plus the "
                         f"stateless local/cloud policies); got {algo!r}")

    mesh = fleet_mesh(mesh, N, device_axis)
    run = _make_sharded_run(mesh, device_axis, rule,
                            per_device_tables=o_tab.ndim == 2,
                            has_overlay=overlay is not None,
                            topo=(None if topo_k is None else
                                  (topo_k.K, topo_k.time_varying)))
    ov_args = (() if overlay is None
               else (overlay.o, overlay.h, overlay.w))
    topo_args = (() if topo_k is None
                 else ((topo_k.assoc_at(0, T) if topo_k.time_varying
                        else topo_k.assoc), topo_k.H_k))
    mu0 = (jnp.float32(0.0) if topo_k is None
           else jnp.zeros((topo_k.K,), jnp.float32))
    off, mu_seq, lnorm, lam, mu, counts = run(
        trace.j_idx, o_tab, h_tab, w_tab, params.B, params.H,
        jnp.zeros((N,), jnp.float32), mu0,
        jnp.zeros((N, M), jnp.float32), jnp.int32(0), *ov_args,
        *topo_args)
    series = _series_from_offloads(trace.j_idx, off, tables, params,
                                   mu_seq, lnorm, overlay,
                                   enforce_slot_capacity,
                                   topology=topology)
    final = onalgo.OnAlgoState(
        lam=lam, mu=mu,
        rho=onalgo.RhoEstimator(counts=counts, t=jnp.int32(T)))
    return series, final


def _sharded_slot(o_t, h_t, w_t, p_local, rule, device_axis, *,
                  has_overlay: bool, topo, assoc=None, H_k=None):
    """The per-slot body shared by EVERY shard_map'd rollout (one-shot,
    streaming, and shard-local-generation runs), so the engines'
    slot dynamics can never drift apart.

    xs is ``(j[, o, h, w][, assoc_t])``; ``topo`` is the static
    ``(K, time_varying)`` pair (None for scalar mu) with ``assoc`` /
    ``H_k`` the closed-over shard-local map and capacities.
    """
    topo_tv = topo is not None and topo[1]

    def slot(state, xs):
        j = xs[0]
        task = j > 0
        if has_overlay:  # raw (unpreconditioned) values; step rescales
            o_now, h_now, w_now = xs[1], xs[2], xs[3]
        else:
            o_now = _lookup(o_t, j)
            h_now = _lookup(h_t, j)
            w_now = _lookup(w_t, j)
        if topo is None:
            state, offload = onalgo.step(state, j, o_now, h_now, w_now,
                                         task, (o_t, h_t, w_t),
                                         p_local, rule,
                                         axis_name=device_axis)
            lam2 = jax.lax.psum(jnp.sum(state.lam**2), device_axis)
            lam_norm = jnp.sqrt(lam2 + state.mu**2)
        else:
            assoc_t = xs[-1] if topo_tv else assoc
            state, offload = onalgo.step(state, j, o_now, h_now, w_now,
                                         task, (o_t, h_t, w_t),
                                         p_local, rule,
                                         axis_name=device_axis,
                                         assoc=assoc_t, H_k=H_k)
            lam2 = jax.lax.psum(jnp.sum(state.lam**2), device_axis)
            lam_norm = jnp.sqrt(lam2 + jnp.sum(state.mu**2))
        return state, (offload, state.mu, lam_norm)

    return slot


def _make_sharded_run(mesh, device_axis: str, rule: StepRule, *,
                      per_device_tables: bool, has_overlay: bool,
                      topo=None):
    """The shard_map'd fleet rollout, resumable from any (state, t0).

    Shared by ``simulate_sharded`` (one call, zero state) and
    ``simulate_sharded_stream`` (one call per workload slab, state
    carried across calls).  lam/counts ride sharded on ``device_axis``;
    mu and the slot counter are replicated scalars; the per-slot load
    psum stays the only cross-shard communication.

    ``topo`` is None or a static ``(K, time_varying)`` pair — the run
    then takes two extra operands (assoc sharded on the device axis,
    H_k replicated), mu becomes the replicated (K,) dual vector, and
    the per-slot collective is the psum of each shard's (K,) segment
    partials.
    """
    tab_spec = P(device_axis, None) if per_device_tables else P(None)
    seq_spec = P(None, device_axis)
    ov_specs = (seq_spec,) * 3 if has_overlay else ()
    _, topo_tv = topo if topo is not None else (None, False)
    topo_specs = ()
    if topo is not None:
        assoc_spec = seq_spec if topo_tv else P(device_axis)
        topo_specs = (assoc_spec, P())

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(seq_spec, tab_spec, tab_spec, tab_spec,
                       P(device_axis), P(), P(device_axis), P(),
                       P(device_axis, None), P()) + ov_specs + topo_specs,
             out_specs=(seq_spec, P(), P(), P(device_axis), P(),
                        P(device_axis, None)),
             check_vma=False)
    def run(j_idx, o_t, h_t, w_t, B, H, lam0, mu0, counts0, t0, *rest):
        assoc = H_k = None
        if topo is not None:
            assoc, H_k = rest[-2:]
            rest = rest[:-2]
        ov = rest
        state = onalgo.OnAlgoState(
            lam=lam0, mu=mu0,
            rho=onalgo.RhoEstimator(counts=counts0, t=t0))
        p_local = OnAlgoParams(B=B, H=H)
        slot = _sharded_slot(o_t, h_t, w_t, p_local, rule, device_axis,
                             has_overlay=has_overlay, topo=topo,
                             assoc=assoc, H_k=H_k)
        xs = (j_idx,) + ov
        if topo is not None and topo_tv:
            xs = xs + (assoc,)
        state, (off, mu_seq, lnorm) = jax.lax.scan(slot, state, xs)
        return (off, mu_seq, lnorm, state.lam, state.mu, state.rho.counts)

    return run


def _make_sharded_stream_run(mesh, device_axis: str, rule: StepRule,
                             source_cols, L: int, local_N: int, *,
                             per_device_tables: bool, has_overlay: bool,
                             topo=None):
    """A shard_map'd slab rollout that GENERATES its own workload columns.

    Unlike :func:`_make_sharded_run` (which consumes a pre-generated
    full-width slab), each shard calls ``source_cols(t0, L, n0,
    local_N)`` with its own column offset ``n0 = axis_index * local_N``
    — the counter-offset draw primitive makes those columns bit-identical
    to slicing a full-width slab, so peak workload-generation memory is
    O(L * N / shards) per shard.  The generated slab (j + overlay
    streams) is returned gathered so the caller's accounting post-pass
    stays engine-independent.
    """
    tab_spec = P(device_axis, None) if per_device_tables else P(None)
    seq_spec = P(None, device_axis)
    n_seq_out = 7 if has_overlay else 2  # off + j (+ 5 overlay streams)
    _, topo_tv = topo if topo is not None else (None, False)
    topo_specs = ()
    if topo is not None:
        assoc_spec = seq_spec if topo_tv else P(device_axis)
        topo_specs = (assoc_spec, P())

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(tab_spec, tab_spec, tab_spec,
                       P(device_axis), P(), P(device_axis), P(),
                       P(device_axis, None), P()) + topo_specs,
             out_specs=(seq_spec,) * n_seq_out
                       + (P(), P(), P(device_axis), P(),
                          P(device_axis, None)),
             check_vma=False)
    def run(o_t, h_t, w_t, B, H, lam0, mu0, counts0, t0, *topo_args):
        n0 = jax.lax.axis_index(device_axis) * local_N
        j_loc, ov_loc = source_cols(t0, L, n0, local_N)
        state = onalgo.OnAlgoState(
            lam=lam0, mu=mu0,
            rho=onalgo.RhoEstimator(counts=counts0, t=t0))
        p_local = OnAlgoParams(B=B, H=H)
        assoc = H_k = None
        if topo is not None:
            assoc, H_k = topo_args
        slot = _sharded_slot(o_t, h_t, w_t, p_local, rule, device_axis,
                             has_overlay=has_overlay, topo=topo,
                             assoc=assoc, H_k=H_k)
        xs = (j_loc,)
        if has_overlay:
            xs = xs + (ov_loc.o, ov_loc.h, ov_loc.w)
        if topo is not None and topo_tv:
            xs = xs + (assoc,)
        state, (off, mu_seq, lnorm) = jax.lax.scan(slot, state, xs)
        ov_out = (() if not has_overlay
                  else (ov_loc.o, ov_loc.h, ov_loc.w, ov_loc.correct_local,
                        ov_loc.correct_cloud))
        return ((off, j_loc) + ov_out
                + (mu_seq, lnorm, state.lam, state.mu, state.rho.counts))

    return run


def simulate_sharded_stream(source, T: int, N: int, tables,
                            params: OnAlgoParams, rule: StepRule, mesh,
                            device_axis: str = "data", *,
                            slab: Optional[int] = None,
                            algo: str = "onalgo",
                            enforce_slot_capacity: bool = False,
                            topology: Optional[Topology] = None,
                            source_cols=None,
                            pipelined: Optional[bool] = None):
    """The sharded engine over a *streamed* workload: no (T, N) horizon.

    Same source contract and memory story as
    :func:`simulate_chunked_stream` — the horizon is walked ``slab``
    slots at a time, each slab generated on device from counters,
    rolled through one jitted shard_map scan resuming from the carried
    (state, t0), and folded into the series before the next slab is
    generated.  Peak memory is O(slab * N) regardless of T.

    ``source_cols(t0, length, n0, n_cols)`` — the column-addressed form
    of the source (e.g. ``StreamingService.slab_cols``) — moves workload
    generation INSIDE the shard_map: each shard generates only its own
    device columns (offset by its ``axis_index``), bit-identical to
    slicing a full-width slab, so peak workload-generation memory drops
    to O(slab * N / shards) per shard.  ``source`` is still used for the
    stateless local/cloud policies.

    ``pipelined`` (default: automatic at N >= 65536) drops every host
    sync and host-side series part from the loop: the rollout's carry
    args are donated, accounting is fused with the series-buffer writes
    into a donated-carry dispatch (:func:`_stream_acct`), and the whole
    series transfers once at the end.  The shard_map rollout itself
    stays its own launch — both walk modes run the same executable, so
    pipelined is bit-identical to the sequential walk by construction.
    """
    span = jax.profiler.TraceAnnotation
    with span(spans.STREAM_PREPARE):
        o_tab, h_tab, w_tab = tables
        M = o_tab.shape[-1]
        mesh = fleet_mesh(mesh, N, device_axis)
        if slab is None:
            slab = 256
        validate_topology(topology, T, N)
        topo_k = _topo_duals(topology)
        topo_static = (None if topo_k is None
                       else (topo_k.K, topo_k.time_varying))
        if pipelined is None:
            pipelined = N >= _PIPELINE_AUTO_N
        stateless = algo in ("local", "cloud")
        if not stateless and algo != "onalgo":
            raise ValueError("the sharded streaming engine rolls OnAlgo "
                             "(plus the stateless local/cloud policies); "
                             f"got {algo!r}")
        if not stateless:
            lam, mu, counts = _stream_state0(None, N, M, topo_k)
            if source_cols is not None:  # shard-local slab generation
                local_N = N // mesh.shape[device_axis]
                L0 = min(slab, T)
                has_overlay = jax.eval_shape(
                    lambda t0, n0: source_cols(t0, L0, n0, local_N),
                    jax.ShapeDtypeStruct((), jnp.int32),
                    jax.ShapeDtypeStruct((), jnp.int32))[1] is not None
                bufs = (_stream_series_buffers(T, topology, has_overlay)
                        if pipelined else None)
    if stateless:
        return _stream_trivial(source, T, N, slab, tables, params, algo,
                               enforce_slot_capacity, topology=topology)

    def topo_args_at(t0, L):
        return (() if topo_k is None
                else ((topo_k.assoc_at(t0, L) if topo_k.time_varying
                       else topo_k.assoc), topo_k.H_k))

    def unpack(out, has_overlay):
        if has_overlay:
            (off, j_slab, ov_o, ov_h, ov_w, ov_cl, ov_cc,
             mu_seq, lnorm, lam, mu, counts) = out
            overlay = RawOverlay(o=ov_o, h=ov_h, w=ov_w,
                                 correct_local=ov_cl, correct_cloud=ov_cc)
        else:
            off, j_slab, mu_seq, lnorm, lam, mu, counts = out
            overlay = None
        return off, j_slab, overlay, mu_seq, lnorm, lam, mu, counts

    parts = []
    if source_cols is not None:
        runs = {}  # one compiled run per distinct slab length

        def make_run(L):
            # lam0/mu0/counts0 (args 5-7) are donated: each slab's carry
            # is dead the moment the next rollout returns.  Both walk
            # modes share this construction so they run the exact same
            # executable — the bit-identity contract rules out fusing
            # the shard_map scan into a larger jit (see _stream_acct).
            return jax.jit(_make_sharded_stream_run(
                mesh, device_axis, rule, source_cols, L, local_N,
                per_device_tables=o_tab.ndim == 2,
                has_overlay=has_overlay, topo=topo_static),
                donate_argnums=(5, 6, 7))

        for t0 in range(0, T, slab):
            with span(spans.STREAM_DISPATCH, t0=t0):
                L = min(slab, T - t0)
                if L not in runs:
                    runs[L] = make_run(L)
                out = runs[L](o_tab, h_tab, w_tab, params.B, params.H, lam,
                              mu, counts, jnp.int32(t0),
                              *topo_args_at(t0, L))
                (off, j_slab, overlay, mu_seq, lnorm,
                 lam, mu, counts) = unpack(out, has_overlay)
                if pipelined:
                    bufs = _stream_acct(
                        bufs, off, j_slab, overlay, mu_seq, lnorm,
                        jnp.int32(t0), tables, params, topology,
                        enforce=enforce_slot_capacity)
                else:
                    parts.append(_series_from_offloads(
                        j_slab, off, tables, params, mu_seq, lnorm,
                        overlay, enforce_slot_capacity, topology=topology,
                        t0=t0))
        final = onalgo.OnAlgoState(
            lam=lam, mu=mu,
            rho=onalgo.RhoEstimator(counts=counts, t=jnp.int32(T)))
        return (bufs if pipelined else _cat_series(parts)), final

    run = None
    bufs = None
    for t0 in range(0, T, slab):
        with span(spans.STREAM_DISPATCH, t0=t0):
            L = min(slab, T - t0)
            # Generation stays an eager per-slab call (service sources
            # are themselves jitted slab launches) — dispatch is async, so
            # the pipelined walk still never syncs inside the loop.
            j_slab, overlay = source(t0, L)
            if run is None:
                # lam0/mu0/counts0 (args 6-8) are donated: the carry is
                # dead once the next rollout returns.  Both walk modes
                # share this construction so they run the exact same
                # executable — the bit-identity contract rules out fusing
                # the shard_map scan into a larger jit (see _stream_acct).
                run = jax.jit(_make_sharded_run(
                    mesh, device_axis, rule,
                    per_device_tables=o_tab.ndim == 2,
                    has_overlay=overlay is not None, topo=topo_static),
                    donate_argnums=(6, 7, 8))
                if pipelined:
                    bufs = _stream_series_buffers(T, topology,
                                                  overlay is not None)
            ov_args = (() if overlay is None
                       else (overlay.o, overlay.h, overlay.w))
            off, mu_seq, lnorm, lam, mu, counts = run(
                j_slab, o_tab, h_tab, w_tab, params.B, params.H, lam, mu,
                counts, jnp.int32(t0), *ov_args, *topo_args_at(t0, L))
            if pipelined:
                bufs = _stream_acct(bufs, off, j_slab, overlay, mu_seq,
                                    lnorm, jnp.int32(t0), tables, params,
                                    topology, enforce=enforce_slot_capacity)
            else:
                parts.append(_series_from_offloads(
                    j_slab, off, tables, params, mu_seq, lnorm, overlay,
                    enforce_slot_capacity, topology=topology, t0=t0))
    final = onalgo.OnAlgoState(
        lam=lam, mu=mu,
        rho=onalgo.RhoEstimator(counts=counts, t=jnp.int32(T)))
    return (bufs if pipelined else _cat_series(parts)), final


@dataclasses.dataclass
class AutotuneResult:
    """The winning chunked-engine configuration and the probe timings."""

    chunk: int
    block_n: Optional[int]
    seconds: float  # best probe wall-time
    timings: dict  # (chunk, block_n[, topo_binned][, slab]) -> seconds
    topology: Optional[Topology] = None  # the topology the probes ran with
    topo_binned: Optional[bool] = None  # winning reduction layout (topo)
    slab: Optional[int] = None  # winning slab length (slabs= probed)

    @property
    def kwargs(self) -> dict:
        """Ready to splat into simulate_chunked / simulate_service.

        When the probes ran under a multi-cloudlet topology, it is part
        of the tuned configuration (K-vector duals change the kernels'
        working set), so it rides along here — as does the winning
        ``topo_binned`` reduction layout, and the winning ``slab``
        length when ``slabs=`` joined the search space.
        """
        kw = {"chunk": self.chunk, "block_n": self.block_n}
        if self.topology is not None:
            kw["topology"] = self.topology
            kw["topo_binned"] = self.topo_binned
        if self.slab is not None:
            kw["slab"] = self.slab
        return kw


def autotune(tables, params: OnAlgoParams, rule: StepRule, *,
             trace: Optional[Trace] = None,
             overlay: Optional[RawOverlay] = None,
             source=None, T: Optional[int] = None, N: Optional[int] = None,
             chunks=(8, 16, 32), block_ns=(None,),
             probe_slots: int = 128, slab: Optional[int] = None,
             slabs=(None,), pipelined: Optional[bool] = None,
             algo: str = "onalgo", enforce_slot_capacity: bool = False,
             repeats: int = 2, warmup: int = 1,
             topology: Optional[Topology] = None,
             topo_binned_opts=None) -> AutotuneResult:
    """Pick (chunk, block_n) for the chunked engines by timing probes.

    Runs a short rollout (the first ``probe_slots`` slots) for every
    candidate in ``chunks`` x ``block_ns`` and returns the fastest —
    wall-clock, steady-state: each candidate runs ``warmup`` untimed
    calls before its ``repeats`` timed ones, so first-call compile time
    never votes in the (chunk, block_n) choice (at small probe horizons
    compiles dominate the rollout by orders of magnitude and would
    otherwise pick whichever candidate happened to trace fastest).
    Probe either a materialized
    ``trace`` (+ optional ``overlay``) or a streaming ``source`` with
    its ``(T, N)``; candidates with ``chunk > probe_slots`` are skipped.

    ``topology`` makes the probes run with the K-vector duals (the
    in-kernel association gathers and segment reductions change the
    working set, so a scalar-tuned (chunk, block_n) may be stale); the
    result carries it so ``AutotuneResult.kwargs`` stays a complete,
    valid engine configuration.  ``topo_binned_opts`` adds the in-kernel
    reduction layout to the search grid: None (default) probes both
    one-hot and binned when the topology has more than one lane bin of
    cloudlets (K > 128, where the (N, K_pad) mask starts to hurt),
    otherwise just the engine default; pass an explicit tuple such as
    ``(False, True)`` to override.

    ``slabs`` adds the streaming slab length to the search grid (source
    probes only): each candidate slab is timed with every
    (chunk, block_n) pair — keys grow a trailing slab element — and the
    winner rides ``AutotuneResult.slab`` / ``.kwargs``.  The default
    ``(None,)`` keeps the legacy grid (the single ``slab=`` value, no
    key change).  ``pipelined`` routes the source probes through the
    pipelined runtime (pass the value the production run will use — the
    fused launch shifts the (chunk, slab) trade-off).
    """
    import time

    if (trace is None) == (source is None):
        raise ValueError("autotune needs exactly one of trace= or source=")
    probe_slab_grid = tuple(slabs) != (None,)
    if trace is not None:
        probe_T = min(trace.T, probe_slots)
        p_trace = Trace(j_idx=trace.j_idx[:probe_T],
                        d_local=trace.d_local[:probe_T])
        p_overlay = None if overlay is None else RawOverlay(
            o=overlay.o[:probe_T], h=overlay.h[:probe_T],
            w=overlay.w[:probe_T],
            correct_local=overlay.correct_local[:probe_T],
            correct_cloud=overlay.correct_cloud[:probe_T])
        p_topo = None if topology is None else topology.prefix(probe_T)
        if probe_slab_grid:
            raise ValueError("slabs= probes the streaming engine; pass "
                             "source= (trace probes have no slab)")

        def probe(chunk, block_n, tb, slab_c):
            return simulate_chunked(p_trace, tables, params, rule,
                                    chunk=chunk, block_n=block_n, algo=algo,
                                    overlay=p_overlay,
                                    enforce_slot_capacity=(
                                        enforce_slot_capacity),
                                    topology=p_topo, topo_binned=tb)
    else:
        if T is None or N is None:
            raise ValueError("autotune(source=...) needs T= and N=")
        probe_T = min(T, probe_slots)

        def probe(chunk, block_n, tb, slab_c):
            return simulate_chunked_stream(
                source, probe_T, N, tables, params, rule, chunk=chunk,
                slab=slab if slab_c is None else slab_c,
                block_n=block_n, algo=algo,
                enforce_slot_capacity=enforce_slot_capacity,
                topology=topology, topo_binned=tb, pipelined=pipelined)

    if repeats < 1 or warmup < 0:
        raise ValueError(f"need repeats >= 1 (got {repeats}) and "
                         f"warmup >= 0 (got {warmup})")
    if topo_binned_opts is None:
        # the reduction layout only matters past one lane bin of
        # cloudlets; below that, probing it would double every grid point
        topo_binned_opts = ((False, True)
                            if topology is not None and topology.K > 128
                            else (None,))
    timings = {}
    for chunk in chunks:
        if chunk > probe_T:
            continue
        for block_n in block_ns:
            for tb in topo_binned_opts:
                for slab_c in slabs:
                    if slab_c is not None and slab_c % chunk:
                        continue  # engine requires slab % chunk == 0
                    key = ((chunk, block_n) if tb is None
                           else (chunk, block_n, tb))
                    if probe_slab_grid:
                        key = key + (slab_c,)
                    for _ in range(warmup):  # compiles don't vote
                        jax.block_until_ready(
                            probe(chunk, block_n, tb, slab_c))
                    best = float("inf")
                    for _ in range(repeats):
                        t_start = time.perf_counter()
                        jax.block_until_ready(
                            probe(chunk, block_n, tb, slab_c))
                        best = min(best, time.perf_counter() - t_start)
                    timings[key] = best
    if not timings:
        raise ValueError(
            f"no viable candidates: chunks={chunks} all exceed the probe "
            f"horizon ({probe_T} slots)")
    best_key, seconds = min(timings.items(), key=lambda kv: kv[1])
    chunk, block_n = best_key[0], best_key[1]
    slab_win = best_key[-1] if probe_slab_grid else None
    mid = best_key[2:-1] if probe_slab_grid else best_key[2:]
    tb_win = mid[0] if mid else None
    return AutotuneResult(chunk=chunk, block_n=block_n, seconds=seconds,
                          timings=timings, topology=topology,
                          topo_binned=tb_win, slab=slab_win)
