"""The main path compiled for a described TPU v5e, without the chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached: it refuses what the interpreter accepts (blocks
not aligned to the (8, 128) tiling, more VMEM than a kernel may use, a
program past the device's memory).  Each test compiles one piece at the
size it runs at on the chip (``interpret=False``), checks that a Pallas
kernel is in the program where one should be, and that the program's
memory fits one chip's 16 GiB.  Nothing runs, so nothing here is a time.

The topology is described inside a fixture, never at import: the TPU
library may be loaded by one process at a time, and every test worker
imports this file.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.kernels import onalgo_step as ks

CITY_N = 2**20
MESH_N = 2**21
M = 73  # the service state space: null + 3 power x 3 cycle x 8 gain levels
HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """Shapes of ``tree``'s arrays (or shapes), placed by ``sharding``."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _check(compiled, kernel: bool):
    """tpu_custom_call present iff a kernel should be; device memory
    under one chip's HBM.  Returns the program's bytes per device."""
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == kernel
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used
    return used


def _rollout(kernel, sharding, N, T, chunk, K=None, **kw):
    """Compile one service-shaped rollout: per-device o (preconditioned),
    shared h/w rows, the raw slot-value overlay, and optionally a
    time-varying K-cloudlet association."""
    topo_kw = {} if K is None else dict(assoc=_i32(T, N), H_k=_f32(K))
    args = _on(sharding, (_i32(T, N), _f32(N),
                          _f32() if K is None else _f32(K), _f32(N, M),
                          _f32(N, M), _f32(M), _f32(M), _f32(N),
                          (_f32(T, N),) * 3, topo_kw, _i32()))

    def run(j, lam, mu, counts, o, h, w, B, sv, topo_kw, t0):
        return kernel(j, lam, mu, counts, o, h, w, B, 1.0, 0.5, 0.5,
                      chunk=chunk, t0=t0, slot_values=sv, interpret=False,
                      **topo_kw, **kw)

    return jax.jit(run).lower(*args).compile()


@pytest.mark.parametrize("K", [None, 1024])
def test_whole_fleet_kernel_at_its_largest_fleet(one_chip, K):
    N = 1024
    assert ks.rollout_block_n(N, M) is None  # still the whole-fleet kernel
    assert ks.rollout_block_n(N + 1, M) == ks._TILE_N
    _check(_rollout(ks.onalgo_chunked_pallas, one_chip, N, 64, 16, K=K),
           kernel=True)


@pytest.mark.parametrize("K", [None, 1024])
def test_tiled_kernel_city_fleet(one_chip, K):
    """N = 2^20 at the chunk the streaming engine runs there; K = 1024
    takes the binned cloudlet layout."""
    assert ks.rollout_block_n(CITY_N, M) == ks._TILE_N
    used = _check(_rollout(ks.onalgo_tiled_pallas, one_chip, CITY_N, 64,
                           64, K=K, block_n=ks._TILE_N), kernel=True)
    assert used < 12 * 2**30


def test_single_slot_duals_kernel(one_chip):
    N = 4096
    args = _on(one_chip, (_f32(N), _f32(), _f32(N, M), _f32(M), _f32(M),
                          _f32(M), _f32(N)))
    compiled = jax.jit(lambda *a: ks.onalgo_duals_pallas(
        *a, interpret=False)).lower(*args).compile()
    _check(compiled, kernel=True)


def _service(N, T):
    """A small compiled service (its tables, rule, pool arrays) plus the
    (SimConfig, pool) describing the same run at fleet size N."""
    from repro.serve.compile import compile_service_streaming
    from repro.serve.simulator import SimConfig, synthetic_pool

    pool = synthetic_pool()
    sim = SimConfig(num_devices=N, T=T, algo="onalgo", B_n=0.06,
                    H=N * 441e6 / 8, seed=7)
    small = compile_service_streaming(
        dataclasses.replace(sim, num_devices=64), pool)
    return sim, pool, small


def test_gateway_tick_city_fleet(one_chip):
    from repro.core import onalgo
    from repro.serve.gateway import make_tick

    sim, _, small = _service(CITY_N, 64)
    tick = make_tick(CITY_N, small.space, topo_duals=False,
                     admit_topo=False, enforce=True)
    state = jax.eval_shape(lambda: onalgo.init_state(CITY_N, M))
    params = dataclasses.replace(small.params, B=_f32(CITY_N))
    bucket = CITY_N  # the largest wave bucket
    args = _on(one_chip, (state, small.tables, params, small.rule,
                          _i32(bucket), _f32(bucket), _f32(bucket),
                          _f32(bucket)))
    compiled = jax.jit(tick, donate_argnums=(0,)).lower(
        *args, None, None).compile()
    _check(compiled, kernel=False)


def test_sharded_slab_step_four_chips(topo):
    """One slab of the sharded streaming engine at N = 2^21 over a 1-D
    mesh of the four described chips, shard-local generation inside."""
    from repro.core import fleet
    from repro.parallel.mesh import make_fleet_mesh
    from repro.workload.streaming import lower_service_workload

    T, L = 128, 64
    sim, pool, small = _service(MESH_N, T)
    mesh = make_fleet_mesh(topo.devices)
    wl = jax.eval_shape(lambda: lower_service_workload(
        sim.seed, T, MESH_N, len(pool.local_correct), small.wl.num_rates,
        tuple(sim.burst_len), sim.mean_gap))
    rep = NamedSharding(mesh, P())
    dev = NamedSharding(mesh, P("data"))
    args = (_on(rep, wl), _on(rep, small.tables), _on(dev, _f32(MESH_N)),
            _on(rep, _f32()), _on(dev, _f32(MESH_N)), _on(rep, _f32()),
            _on(NamedSharding(mesh, P("data", None)), _f32(MESH_N, M)),
            _on(rep, _i32()))

    def step(wl, tables, B, H, lam, mu, counts, t0):
        ss = dataclasses.replace(small, sim=sim, wl=wl)
        run = fleet._make_sharded_stream_run(
            mesh, "data", small.rule, ss.slab_cols, L, MESH_N // 4,
            per_device_tables=False, has_overlay=True)
        return run(*tables, B, H, lam, mu, counts, t0)

    compiled = jax.jit(step).lower(*args).compile()
    _check(compiled, kernel=False)
    assert "all-reduce" in compiled.as_text()  # the per-slot load psum
    np.testing.assert_equal(len(compiled.input_shardings[0][2].device_set),
                            4)
