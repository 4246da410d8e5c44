"""The replay engine's layer names in what the profiler sees.

Device scopes (``repro.spans.SCOPES``) reach each layer's operations in
the lowered slab step; the Pallas rollout kernels lower to custom calls
under stable names; and a pipelined streaming walk writes its host spans
(``repro.stream.*``) into the profiler's trace, one dispatch span per
slab carrying the slab's first slot.
"""

import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro import spans
from repro.core import fleet
from repro.kernels import onalgo_step as ks
from repro.serve.compile import compile_service_streaming
from repro.serve.simulator import SimConfig, synthetic_pool

N, M = 256, 73


def _service(T):
    sim = SimConfig(num_devices=N, T=T, algo="onalgo", B_n=0.06,
                    H=N * 441e6 / 2, seed=11)
    return compile_service_streaming(sim, synthetic_pool())


@pytest.fixture(scope="module")
def slab_step_text():
    """The pipelined slab step (one 64-slot slab, K = 1, admission on),
    compiled: the text XLA keeps each op's ``op_name`` in."""
    ss = _service(128)
    M_ = int(ss.tables[0].shape[-1])
    bufs = fleet._stream_series_buffers(128, None, True)
    carry = (jnp.zeros((N,), jnp.float32), jnp.float32(0.0),
             jnp.zeros((N, M_), jnp.float32), bufs)
    lowered = fleet._pipelined_slab_step.lower(
        carry, jnp.int32(0), jnp.int32(0), ss.tables, ss.params, ss.rule,
        None, src=fleet._StaticSource(ss.slab_aligned), L=64, chunk=64,
        block_n=None, enforce_slot_capacity=True, topo_binned=None)
    return lowered.compile().as_text()


@pytest.mark.parametrize("scope", spans.SCOPES)
def test_slab_step_ops_carry_scope(slab_step_text, scope):
    assert re.search(f'op_name="[^"]*/{re.escape(scope)}/', slab_step_text)


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _duals(lam, mu, counts, o, h, w, B):
    return ks.onalgo_duals_pallas(lam, mu, counts, o, h, w, B,
                                  interpret=False)


def _rollout(kernel, **kw):
    def run(lam, mu, counts, o, h, w, B):
        j = jnp.zeros((64, N), jnp.int32)
        sv = (jnp.zeros((64, N), jnp.float32),) * 3
        return kernel(j, lam, mu, counts, o, h, w, B, 1.0, 0.5, 0.5,
                      chunk=64, t0=0, slot_values=sv, interpret=False, **kw)
    return run


@pytest.mark.parametrize("name, fn", [
    ("onalgo_duals", _duals),
    ("onalgo_chunked", _rollout(ks.onalgo_chunked_pallas)),
    ("onalgo_tiled", _rollout(ks.onalgo_tiled_pallas, block_n=128)),
])
def test_pallas_calls_carry_their_names(name, fn):
    """Lowered for the TPU (no chip needed): each kernel is one
    ``tpu_custom_call`` under its own name."""
    args = (_f32(N), _f32(), _f32(N, M), _f32(M), _f32(M), _f32(M),
            _f32(N))
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 1
    assert f'kernel_name = "{name}"' in text


def _walk(ss, T, walk):
    if walk == "sharded":
        return fleet.simulate_sharded_stream(
            ss.slab, T, N, ss.tables, ss.params, ss.rule,
            jax.make_mesh((1,), ("data",)), slab=64,
            enforce_slot_capacity=True, source_cols=ss.slab_cols,
            pipelined=True)
    return fleet.simulate_chunked_stream(
        ss.slab, T, N, ss.tables, ss.params, ss.rule, chunk=64, slab=64,
        enforce_slot_capacity=True, pipelined=walk == "pipelined",
        source_aligned=ss.slab_aligned)


@pytest.mark.parametrize("walk, t0s, tails", [
    ("pipelined", [0, 64, 128], 1), ("sequential", [0, 64, 128], 1),
    ("sharded", [0, 64, 128, 192], 0)])
def test_stream_host_spans_in_the_profile(tmp_path, walk, t0s, tails):
    """A 200-slot walk in 64-slot slabs under the profiler: one prepare
    span, one dispatch span per slab carrying its first slot, and for the
    chunked walks one span for the 8-slot tail (the sharded walk rolls
    it as a short slab)."""
    T = 200
    ss = _service(T)

    def run():
        series, state = _walk(ss, T, walk)
        return jax.block_until_ready((series, state.lam))

    run()  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        run()
    files = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    assert len(files) == 1
    found = {name: [] for name in spans.STREAM_SPANS}
    for plane in jax.profiler.ProfileData.from_file(files[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in found:
                    found[ev.name].append({k: v for k, v in ev.stats})
    assert len(found[spans.STREAM_PREPARE]) == 1
    assert len(found[spans.STREAM_TAIL]) == tails
    assert sorted(s["t0"] for s in found[spans.STREAM_DISPATCH]) == t0s
