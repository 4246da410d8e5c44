"""OnAlgo — the paper's online selective-offloading algorithm (Algorithm 1).

Per slot t, with dual variables (lambda_t in R^N_+, mu_t in R_+):

  primal (threshold rule, eq. 7):
      offload device n's task in state j  iff  lambda_nt*o_n^j + mu_t*h_n^j < w_n^j

  dual ascent (eqs. 8-9), using the *policy over all states* weighted by the
  running empirical distribution rho_t:
      lambda_{n,t+1} = [lambda_nt + a_t (sum_j o_n^j rho_t^j y_n^j - B_n)]^+
      mu_{t+1}       = [mu_t + a_t (sum_n sum_j h_n^j rho_t^j y_n^j - H)]^+

The mu update couples all devices through a single scalar sum — in the
distributed fleet (fleet.py / shard_map over the mesh ``data`` axis) this is
one ``psum``, i.e. exactly the paper's "lightweight protocol" (cloudlet
broadcasts mu, devices report their load contribution).

Everything here is jit/scan-compatible: OnAlgoState is a registered dataclass
pytree and ``step`` is a pure function.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.state_space import RhoEstimator


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class StepRule:
    """Dual step-size rule a_t = a / t^beta (beta=0 -> constant; 0.5 -> 1/sqrt(t))."""

    a: jax.Array  # scalar float
    beta: jax.Array  # scalar float in [0, 1)

    @staticmethod
    def constant(a: float) -> "StepRule":
        return StepRule(jnp.float32(a), jnp.float32(0.0))

    @staticmethod
    def inv_sqrt(a: float) -> "StepRule":
        return StepRule(jnp.float32(a), jnp.float32(0.5))

    @staticmethod
    def power(a: float, beta: float) -> "StepRule":
        return StepRule(jnp.float32(a), jnp.float32(beta))

    def at(self, t: jax.Array) -> jax.Array:
        tf = jnp.maximum(t, 1).astype(jnp.float32)
        return self.a / tf**self.beta


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class OnAlgoParams:
    """Problem constants: per-device power budgets and cloudlet capacity.

    B: (N,) average power budgets (Watts) — constraint (3).
    H: scalar average cloudlet capacity (cycles/s or FLOP/s) — constraint (4).
       In a sharded fleet H is the *global* capacity; the shard-local update
       psums the load first.

    ``precondition`` (static in spirit; stored as a traced bool-like float is
    avoided — keep it a plain Python bool) rescales each constraint row to
    RHS = 1 (o' = o/B_n, h' = h/H).  This is an exact diagonal preconditioner
    of the dual ascent: decisions are unchanged for correspondingly-rescaled
    duals, but a single O(1) step size then works across constraints whose
    physical units differ by 9 orders of magnitude (Watts vs cycles/s).  Set
    False for the paper-literal update (then a_t must be hand-tuned per
    deployment).
    """

    B: jax.Array
    H: jax.Array
    precondition: bool = dataclasses.field(default=True,
                                           metadata={"static": True})


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class OnAlgoState:
    lam: jax.Array  # (N,) power duals  lambda_nt
    mu: jax.Array  # () cloudlet capacity dual mu_t — or (K,) per-cloudlet
    rho: RhoEstimator  # streaming empirical per-device state distribution


def init_state(num_devices: int, M: int,
               K: Optional[int] = None) -> OnAlgoState:
    """Fresh duals: mu is scalar, or (K,) for a K-cloudlet topology."""
    return OnAlgoState(
        lam=jnp.zeros((num_devices,), jnp.float32),
        mu=jnp.zeros(() if K is None else (K,), jnp.float32),
        rho=RhoEstimator.create(num_devices, M),
    )


def risk_adjusted_gain(phi_hat, sigma, v_risk):
    """Eq. (1): w = clip(phi_hat - v * sigma, 0, 1).

    The ONE definition of the risk-adjusted offloading gain — the service
    lowering (``serve.compile._lower_values``) and every
    :mod:`repro.gain` source (table / overlay / model) route through this
    function, so a gain estimate pre-folded into a table is bit-identical
    to the same expression fused into the per-slot gather path.
    Elementwise float ops only: commutes exactly with gathers.
    """
    return jnp.clip(phi_hat - v_risk * sigma, 0.0, 1.0)


def precondition_tables(o_tab, h_tab, params: OnAlgoParams):
    """Constraint-space tables: (o', h', B_eff, H_eff).

    With ``params.precondition`` each constraint row is rescaled to RHS 1
    (o' = o/B_n — broadcasting (M,) tables to (N, M) — and h' = h/H);
    otherwise a passthrough.  Every consumer of the dual space (step, the
    Theorem-1 series, the chunked kernel) must use THIS helper so the
    scaling can never desynchronize between paths.
    """
    if not params.precondition:
        return o_tab, h_tab, params.B, params.H
    B_col = params.B[:, None] if params.B.ndim == 1 else params.B
    return (o_tab / B_col, h_tab / params.H,
            jnp.ones_like(params.B), jnp.ones_like(params.H))


def policy_matrix(lam, mu, o_tab, h_tab, w_tab, assoc=None):
    """Threshold policy y in {0,1}^(N,M) for EVERY state (eq. 6/7).

    Tables broadcast: (M,) shared or (N, M) per-device.  Returned as float32
    so downstream reductions are dtype-stable.

    With a multi-cloudlet topology, ``mu`` is the (K,) dual vector and
    ``assoc`` (N,) selects each device's *current* cloudlet price.
    """
    if assoc is None:
        price = lam[:, None] * o_tab + mu * h_tab  # (N, M)
    else:
        price = lam[:, None] * o_tab + mu[assoc][:, None] * h_tab
    return (price < w_tab).astype(jnp.float32) * (w_tab > 0)


def decide(lam, mu, o_now, h_now, w_now, task_mask):
    """Realized offloading decision for the CURRENT state values (eq. 7).

    o_now/h_now/w_now: (N,) current-slot values; task_mask: (N,) bool.
    ``mu`` is the scalar capacity dual, or an already-gathered (N,)
    per-device price ``mu_k[assoc]`` under a multi-cloudlet topology
    (broadcasting covers both).  A device with w<=0 never offloads
    (paper footnote 4: if the cloudlet is not expected to improve
    accuracy, w_nt = 0 and lam*o+mu*h < 0 is impossible since duals are
    non-negative).
    """
    price = lam * o_now + mu * h_now
    return (price < w_now) & (w_now > 0) & task_mask


def decision_margin(state: OnAlgoState, o_now, h_now, w_now,
                    params: OnAlgoParams, assoc=None):
    """w - (lam o + mu h) for the current slot under the duals
    ``state`` carries into it, in the space the duals live in: the
    quantity whose sign :func:`step`'s realized decision thresholds
    (a device with w > 0 and a task offloads iff it is positive).
    ``assoc`` (N,) gathers each device's cloudlet dual from a (K,) mu."""
    if params.precondition:
        o_now = o_now / params.B
        h_now = h_now / params.H
    mu_n = state.mu[assoc] if assoc is not None else state.mu
    return w_now - (state.lam * o_now + mu_n * h_now)


def constraint_slacks(y_pol, rho, o_tab, h_tab, params: OnAlgoParams,
                      axis_name: Optional[str] = None):
    """g_t(y): per-device power slack (N,) and global capacity slack ().

    With ``axis_name`` set (inside shard_map), the capacity term is psum'd
    across fleet shards — this is the single collective of the protocol.
    """
    o_full = jnp.broadcast_to(o_tab, y_pol.shape)
    h_full = jnp.broadcast_to(h_tab, y_pol.shape)
    g_pow = jnp.sum(o_full * rho * y_pol, axis=-1) - params.B  # (N,)
    load = jnp.sum(h_full * rho * y_pol)
    if axis_name is not None:
        load = jax.lax.psum(load, axis_name)
    g_cap = load - params.H  # ()
    return g_pow, g_cap


def capacity_loads(y_pol, rho, h_tab, assoc, K: int,
                   axis_name: Optional[str] = None):
    """(K,) per-cloudlet expected loads of the policy under rho.

    Each device's row load (sum over states of h * rho * y) is
    segment-reduced onto its cloudlet via the (N,) ``assoc`` ids.  With
    ``axis_name`` set (inside shard_map), the (K,) partials are psum'd
    across fleet shards — the association may cross shard boundaries
    freely, and the per-slot collective stays one K-vector.
    """
    h_full = jnp.broadcast_to(h_tab, y_pol.shape)
    rows = jnp.sum(h_full * rho * y_pol, axis=-1)  # (N,)
    load = jax.ops.segment_sum(rows, assoc, num_segments=K)
    if axis_name is not None:
        load = jax.lax.psum(load, axis_name)
    return load


def step(state: OnAlgoState,
         j_idx: jax.Array,
         o_now: jax.Array,
         h_now: jax.Array,
         w_now: jax.Array,
         task_mask: jax.Array,
         tables,
         params: OnAlgoParams,
         rule: StepRule,
         axis_name: Optional[str] = None,
         use_kernel: bool = False,
         assoc: Optional[jax.Array] = None,
         H_k: Optional[jax.Array] = None):
    """One OnAlgo slot (Algorithm 1 lines 3-19).

    Args:
      state: OnAlgoState at slot t (duals lambda_t, mu_t; rho up to t-1).
      j_idx: (N,) int32 current per-device state indices.
      o_now/h_now/w_now: (N,) realized current-slot values (what the device
        observes: channel-dependent power, image-size-dependent cycles,
        predictor gain).
      task_mask: (N,) bool — False where s_nt = null.
      tables: (o_tab, h_tab, w_tab) quantized value tables, (M,) or (N, M).
      params/rule: problem constants and step rule.
      axis_name: mesh axis for the distributed-fleet psum.
      use_kernel: route the fused policy+reduction through the Pallas kernel
        (kernels/onalgo_step.py) instead of the jnp path.
      assoc / H_k: multi-cloudlet topology slot — (N,) int32 current
        cloudlet ids and (K,) capacities.  ``state.mu`` must then be the
        (K,) dual vector: each device is priced by its own cloudlet's
        entry and the capacity ascent runs per cloudlet on the
        segment-reduced loads.  ``params.H`` stays the preconditioner
        reference scale (h' = h / params.H, H_k' = H_k / params.H).

    Returns:
      (new_state, offload (N,) bool)
    """
    topo = assoc is not None
    if topo != (H_k is not None):
        raise ValueError("assoc and H_k must be passed together")
    if topo and use_kernel:
        raise ValueError(
            "use_kernel (the fused single-slot dual kernel) does not "
            "support multi-cloudlet duals; run with use_kernel=False or "
            "through the chunked engines")
    o_tab, h_tab, w_tab = tables
    if params.precondition:
        # Diagonal preconditioner: each constraint row normalized to RHS 1.
        o_tab, h_tab, B_eff, H_eff = precondition_tables(o_tab, h_tab,
                                                         params)
        o_now = o_now / params.B
        h_now = h_now / params.H
        if topo:
            H_k = H_k / params.H
        params = OnAlgoParams(B=B_eff, H=H_eff, precondition=False)

    # --- line 5-8: observe state, update running distribution (rho includes t)
    rho_est = state.rho.update(j_idx)
    rho = rho_est.rho
    t = rho_est.t

    # --- line 9-11: realized threshold decision under (lambda_t, mu_t)
    mu_n = state.mu[assoc] if topo else state.mu
    offload = decide(state.lam, mu_n, o_now, h_now, w_now, task_mask)

    # --- lines 13 & 17: dual subgradient from the full policy (eq. 6)
    if use_kernel:
        from repro.kernels import ops as kops
        g_pow, load = kops.onalgo_duals(state.lam, state.mu, rho, o_tab,
                                        h_tab, w_tab, params.B)
        if axis_name is not None:
            load = jax.lax.psum(load, axis_name)
        g_cap = load - params.H
    elif topo:
        y_pol = policy_matrix(state.lam, state.mu, o_tab, h_tab, w_tab,
                              assoc=assoc)
        o_full = jnp.broadcast_to(o_tab, y_pol.shape)
        g_pow = jnp.sum(o_full * rho * y_pol, axis=-1) - params.B  # (N,)
        load_k = capacity_loads(y_pol, rho, h_tab, assoc, H_k.shape[0],
                                axis_name)
        g_cap = load_k - H_k  # (K,)
    else:
        y_pol = policy_matrix(state.lam, state.mu, o_tab, h_tab, w_tab)
        g_pow, g_cap = constraint_slacks(y_pol, rho, o_tab, h_tab, params,
                                         axis_name)

    a_t = rule.at(t)
    lam = jnp.maximum(state.lam + a_t * g_pow, 0.0)
    mu = jnp.maximum(state.mu + a_t * g_cap, 0.0)

    return OnAlgoState(lam=lam, mu=mu, rho=rho_est), offload
