"""OnAlgo as the serving tier's admission controller (the paper's technique
as a first-class framework feature).

The cloudlet-capacity dual mu is a *congestion price* the serving tier
broadcasts to the fleet each slot; per-device power duals lambda_n stay
device-local.  Request costs h are expressed in model FLOPs of the serving
architecture (per-arch values come from the roofline analysis), so the same
controller drives any of the 10 cloudlet models; H is the pod's sustained
FLOP/s budget per slot.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import onalgo
from repro.core.onalgo import OnAlgoParams, StepRule
from repro.core.state_space import StateSpace


def nearest_level(x, levels) -> jax.Array:
    """Index of the level nearest each value of ``x``: float32 distances,
    ties to the first level.  Elementwise in ``x``, so it commutes exactly
    with a lookup (the service lowering quantizes its (S,) tables with
    it, then looks the indices up)."""
    lv = jnp.asarray(levels, jnp.float32)
    return jnp.argmin(jnp.abs(jnp.asarray(x, jnp.float32)[..., None] - lv),
                      axis=-1)


@partial(jax.jit, static_argnames=("space",))
def quantize_states_device(space: StateSpace, o, h, w, task_mask
                           ) -> jax.Array:
    """Device-side :func:`quantize_states`: one fused jitted pass from raw
    (o, h, w, task) to int32 state indices, jit-composable.  ``space`` is
    static (frozen/hashable), so the level grids fold into the program as
    constants; ``StateSpace.encode`` stays the single source of truth for
    the state layout.  Nearest-level ties break to the first level, in
    float32 distances (:func:`nearest_level`)."""
    io = nearest_level(o, space.o_levels)
    ih = nearest_level(h, space.h_levels)
    iw = nearest_level(w, space.w_levels)
    j = space.encode(io, ih, iw).astype(jnp.int32)
    return jnp.where(jnp.asarray(task_mask, bool), j, jnp.int32(0))


def quantize_states(space: StateSpace, o, h, w, task_mask) -> np.ndarray:
    """Map raw (o, h, w) values to nearest state indices (0 = no task).

    Accepts any matching batch shape — (N,) for one controller slot,
    (T, N) for a whole compiled service horizon — in one jitted
    nearest-level + encode kernel (:func:`quantize_states_device`).
    Ties break to the first level, like the numpy argmin this replaced;
    distances are computed in float32, so values within a float32 ulp of
    a level midpoint may round differently than the old float64 host
    path.
    """
    return np.asarray(quantize_states_device(space, o, h, w, task_mask))


@dataclasses.dataclass
class AdmissionController:
    """Vectorized OnAlgo over a fleet of N devices, driven slot by slot with
    RAW (unquantized) observed values; the quantized state space is used for
    the running distribution rho_t exactly as in the paper."""

    space: StateSpace
    params: OnAlgoParams
    rule: StepRule
    num_devices: int
    use_kernel: bool = False

    def __post_init__(self):
        self.state = onalgo.init_state(self.num_devices, self.space.M)
        self.tables = self.space.tables()
        self._step = jax.jit(partial(
            onalgo.step, tables=self.tables, params=self.params,
            rule=self.rule, use_kernel=self.use_kernel))

    def quantize(self, o, h, w, task_mask):
        """Map raw (o, h, w) to the nearest state index (0 = no task)."""
        return quantize_states(self.space, o, h, w, task_mask)

    def admit(self, o, h, w, task_mask):
        """One slot. All args (N,) float/bool. Returns offload mask (N,)."""
        j = self.quantize(o, h, w, task_mask)
        self.state, offload = self._step(
            self.state, jnp.asarray(j), jnp.asarray(o, jnp.float32),
            jnp.asarray(h, jnp.float32), jnp.asarray(w, jnp.float32),
            jnp.asarray(task_mask))
        return np.asarray(offload)

    @property
    def mu(self) -> float:
        return float(self.state.mu)

    @property
    def lam(self) -> np.ndarray:
        return np.asarray(self.state.lam)


def flops_per_request(cfg, seq_len: int, mode: str = "prefill") -> float:
    """Serving cost h for one request against architecture ``cfg``:
    2 * active_params * tokens (decode: per generated token)."""
    n_active = cfg.active_param_count()
    tokens = seq_len if mode == "prefill" else 1
    return 2.0 * n_active * tokens
