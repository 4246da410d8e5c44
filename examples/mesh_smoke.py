"""Multi-device mesh smoke: the sharded paths on a forced CPU mesh.

Exercises ``fleet.simulate_sharded_stream`` (shard-local workload
generation via ``source_cols``) and the live gateway's jitted tick with
mesh-sharded persistent state on a 4-device host-platform mesh, checking
both against their single-logic references.  CI runs this on every PR
with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``; run
standalone without the flag and the script forces it itself (set
``MESH_SMOKE_DEVICES`` to change the count).

    PYTHONPATH=src python examples/mesh_smoke.py
"""

import os

DEVICES = int(os.environ.get("MESH_SMOKE_DEVICES", "4"))
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={DEVICES}")

import jax  # noqa: E402  (after XLA_FLAGS)
import numpy as np  # noqa: E402

from repro.core import fleet  # noqa: E402
from repro.launch.mesh import make_test_mesh  # noqa: E402
from repro.serve.compile import compile_service_streaming  # noqa: E402
from repro.serve.gateway import GatewayCore  # noqa: E402
from repro.serve.simulator import SimConfig, synthetic_pool  # noqa: E402
from repro.workload.loadgen import ServiceLoadGen  # noqa: E402

N, T = 64, 128


def main():
    n_dev = jax.device_count()
    assert n_dev == DEVICES, (
        f"expected {DEVICES} host devices, got {n_dev} — is another "
        f"XLA_FLAGS device count already active?")
    mesh = make_test_mesh((n_dev,), ("data",))
    pool = synthetic_pool()
    sim = SimConfig(num_devices=N, T=T, algo="onalgo", seed=9)
    ss = compile_service_streaming(sim, pool)
    print(f"== mesh smoke: {n_dev}-device CPU mesh, N={N}, T={T} ==")

    # 1. streaming sharded engine, shard-local workload generation
    series, _ = fleet.simulate_chunked_stream(
        ss.slab, T, N, ss.tables, ss.params, ss.rule, chunk=16, slab=64)
    s_sh, _ = fleet.simulate_sharded_stream(
        ss.slab, T, N, ss.tables, ss.params, ss.rule, mesh, slab=64,
        source_cols=ss.slab_cols)
    for k in ("reward", "power", "load", "offloads", "mu"):
        np.testing.assert_allclose(np.asarray(s_sh[k]),
                                   np.asarray(series[k]), rtol=1e-4,
                                   atol=1e-5, err_msg=k)
    print("  simulate_sharded_stream (source_cols): matches chunked")

    # 2. gateway tick with mesh-sharded persistent state
    ref = GatewayCore.for_service(ss)
    sh = GatewayCore.for_service(ss, mesh=mesh)
    lg = ServiceLoadGen(ss)
    for wv in lg.waves(0, T):
        o_r, a_r = ref.tick(wv.idx, wv.o, wv.h, wv.w)
        o_s, a_s = sh.tick(wv.idx, wv.o, wv.h, wv.w)
        assert np.array_equal(o_r, o_s) and np.array_equal(a_r, a_s), wv.t
    assert np.array_equal(np.asarray(ref.state.lam),
                          np.asarray(sh.state.lam))
    print(f"  gateway tick on mesh: {T} slots bit-identical "
          f"(state sharding: {sh.state.lam.sharding})")
    print("mesh smoke: OK")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
