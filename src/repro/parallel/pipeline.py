"""GPipe-style pipeline parallelism over a mesh axis (designed for 'pod').

When inter-pod links are much slower than intra-pod ICI, pure DP over pods
pays a full gradient all-reduce per step; pipelining the layer stack across
pods sends only activations (one microbatch per tick) over the slow links.

``pipeline_apply`` runs the canonical GPipe schedule inside ``shard_map``:
stage s owns its slice of the layer stack; each tick, activations hop to the
next stage via ``lax.ppermute`` while new microbatches stream into stage 0.
M microbatches over S stages take M + S - 1 ticks (bubble fraction
(S-1)/(M+S-1)).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn, stage_params, microbatches, mesh,
                   axis: str = "pod"):
    """Run microbatches through S pipeline stages.

    stage_fn: (params_slice, h) -> h  (one stage's computation)
    stage_params: pytree with leading dim S (= mesh.shape[axis])
    microbatches: (M, *batch_shape) — all enter stage 0 in order.
    Returns (M, *batch_shape), replicated across the axis.
    """
    S = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    M = microbatches.shape[0]

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(axis), P()),
             out_specs=P(), check_vma=False)
    def run(params, x):
        local = jax.tree.map(lambda p: p[0], params)  # this stage's params
        sid = jax.lax.axis_index(axis)
        h0 = jnp.zeros_like(x[0])
        outputs0 = jnp.zeros_like(x)

        def tick(carry, t):
            h_in, outputs = carry
            # stage 0 pulls the next microbatch; others use the received act
            m_in = jnp.clip(t, 0, M - 1)
            x_t = jax.lax.dynamic_index_in_dim(x, m_in, 0, keepdims=False)
            inp = jnp.where(sid == 0, x_t, h_in)
            h_out = stage_fn(local, inp)
            # ship to the next stage (stage S-1 sends nowhere)
            perm = [(i, i + 1) for i in range(S - 1)]
            h_next = jax.lax.ppermute(h_out, axis, perm)
            # last stage retires microbatch t - (S-1)
            m_out = t - (S - 1)
            idx = jnp.clip(m_out, 0, M - 1)
            cur = jax.lax.dynamic_index_in_dim(outputs, idx, 0,
                                               keepdims=False)
            take = (m_out >= 0) & (m_out < M) & (sid == S - 1)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(take, h_out, cur), idx, 0)
            return (h_next, outputs), None

        (_, outputs), _ = jax.lax.scan(
            tick, (h0, outputs0), jnp.arange(M + S - 1))
        # only the last stage holds real outputs; psum replicates them
        outputs = outputs * jnp.where(sid == S - 1, 1.0, 0.0).astype(
            outputs.dtype)
        return jax.lax.psum(outputs, axis)

    return run(stage_params, microbatches)


def bubble_fraction(num_microbatches: int, num_stages: int) -> float:
    return (num_stages - 1) / (num_microbatches + num_stages - 1)
