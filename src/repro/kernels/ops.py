"""Public jit'd wrappers for the Pallas kernels.

The kernels lower natively on a TPU and run through the Pallas
interpreter on the CPU (tests, a build box without the chip); the
platform decides, and no other platform is served.
"""

from __future__ import annotations

from functools import partial

import jax

from repro import spans


def interpret_mode() -> bool:
    """True exactly when JAX's default platform is the CPU: the kernels
    then run through the Pallas interpreter.  Asked lazily (it
    initializes the backend); any platform but ``cpu`` or ``tpu`` raises.
    """
    platform = jax.default_backend()
    if platform not in ("cpu", "tpu"):
        raise RuntimeError(
            f"the Pallas kernels run on a TPU, or interpreted on the CPU; "
            f"JAX's platform is {platform!r}")
    return platform == "cpu"


@partial(jax.jit, static_argnames=())
def onalgo_duals(lam, mu, rho, o_tab, h_tab, w_tab, B):
    from repro.kernels.onalgo_step import onalgo_duals_pallas
    return onalgo_duals_pallas(lam, mu, rho, o_tab, h_tab, w_tab, B,
                               interpret=interpret_mode())


@partial(jax.jit, static_argnames=("chunk", "topo_binned"))
def onalgo_chunked(j_seq, lam0, mu0, counts0, o_tab, h_tab, w_tab, B, H,
                   a, beta, *, chunk=8, t0=0, slot_values=None,
                   assoc=None, H_k=None, topo_binned=None):
    """Fused multi-slot OnAlgo rollout (see onalgo_step.onalgo_chunked_pallas).

    ``slot_values``: optional (o, h, w) raw (T, N) streams (service
    overlay, dual space) driving the realized decision.  ``t0`` is
    traced: slab launches resuming at different offsets share one
    compile (the streaming engines).  ``assoc`` / ``H_k``: optional
    multi-cloudlet topology — (T, N) cloudlet ids + (K,) capacities;
    mu0 and the mu outputs are then (K,)-vectors.  ``topo_binned``
    selects the binned (hi, lo) topology reduction (None = auto by K)."""
    from repro.kernels.onalgo_step import onalgo_chunked_pallas
    with jax.named_scope(spans.ROLLOUT):
        return onalgo_chunked_pallas(
            j_seq, lam0, mu0, counts0, o_tab, h_tab, w_tab, B, H, a, beta,
            chunk=chunk, t0=t0, slot_values=slot_values, assoc=assoc,
            H_k=H_k, topo_binned=topo_binned, interpret=interpret_mode())


@partial(jax.jit, static_argnames=("chunk", "block_n", "topo_binned"))
def onalgo_tiled(j_seq, lam0, mu0, counts0, o_tab, h_tab, w_tab, B, H,
                 a, beta, *, chunk=8, block_n=256, t0=0, slot_values=None,
                 assoc=None, H_k=None, topo_binned=None):
    """Device-tiled fused rollout (see onalgo_step.onalgo_tiled_pallas):
    same results as ``onalgo_chunked`` with O(block_n * M) VMEM."""
    from repro.kernels.onalgo_step import onalgo_tiled_pallas
    with jax.named_scope(spans.ROLLOUT):
        return onalgo_tiled_pallas(
            j_seq, lam0, mu0, counts0, o_tab, h_tab, w_tab, B, H, a, beta,
            chunk=chunk, block_n=block_n, t0=t0, slot_values=slot_values,
            assoc=assoc, H_k=H_k, topo_binned=topo_binned,
            interpret=interpret_mode())


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal=True, block_q=128, block_k=128):
    from repro.kernels.flash_attention import flash_attention_pallas
    return flash_attention_pallas(q, k, v, causal=causal, block_q=block_q,
                                  block_k=block_k, interpret=interpret_mode())


@partial(jax.jit, static_argnames=("block_k",))
def decode_attention(q, k_cache, v_cache, cache_len, *, block_k=128):
    from repro.kernels.decode_attention import decode_attention_pallas
    return decode_attention_pallas(q, k_cache, v_cache, cache_len,
                                   block_k=block_k, interpret=interpret_mode())


@jax.jit
def ssd_chunk(x, dt, A, Bh, Ch):
    from repro.kernels.ssd_chunk import ssd_chunk_pallas
    return ssd_chunk_pallas(x, dt, A, Bh, Ch, interpret=interpret_mode())
