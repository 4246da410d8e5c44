"""Multi-device behaviour, via subprocesses with forced host device counts
(the main test process must keep a single CPU device)."""

import os
import subprocess
import sys
import textwrap

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 520):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n_devices}")
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


class TestShardedFleet:
    def test_sharded_onalgo_matches_single_device(self):
        """The distributed fleet (shard_map + psum for mu) produces the same
        duals/rewards as the single-process simulation."""
        out = run_with_devices("""
            import numpy as np, jax, jax.numpy as jnp
            from repro.core import (OnAlgoParams, StepRule,
                                    default_paper_space, simulate,
                                    simulate_sharded)
            from repro.core.fleet import Trace
            from repro.data.traces import TraceSpec, iid_trace
            from repro.launch.mesh import make_test_mesh

            space = default_paper_space(num_w=4)
            N, T = 16, 200
            trace, _ = iid_trace(space, TraceSpec(T=T, N=N, seed=2))
            tables = space.tables()
            params = OnAlgoParams(B=jnp.full((N,), 0.08),
                                  H=jnp.float32(7e8))
            rule = StepRule.inv_sqrt(0.5)
            series, fin = simulate(trace, tables, params, rule)

            mesh = make_test_mesh((4, 2), ("data", "model"))
            s_sh, fin_sh = simulate_sharded(trace, tables, params,
                                            rule, mesh,
                                            device_axis="data")
            assert set(s_sh) == set(series)
            for k in ("reward", "power", "load", "offloads", "tasks",
                      "mu", "lam_norm"):
                np.testing.assert_allclose(np.asarray(s_sh[k]),
                                           np.asarray(series[k]),
                                           rtol=1e-4, atol=1e-5,
                                           err_msg=k)
            np.testing.assert_allclose(np.asarray(fin_sh.lam),
                                       np.asarray(fin.lam), rtol=1e-4,
                                       atol=1e-6)
            np.testing.assert_allclose(float(fin_sh.mu),
                                       float(fin.mu), rtol=1e-4, atol=1e-7)
            print("OK")
        """)
        assert "OK" in out

    def test_sharded_overlay_matches_single_device(self):
        """The service overlay's raw decision streams shard correctly:
        across 4 real shards, simulate_sharded(overlay=...) reproduces
        the single-process scan engine series for series (incl. the
        ``correct`` accounting and the admission post-pass)."""
        out = run_with_devices("""
            import numpy as np, jax, jax.numpy as jnp
            from repro.core import (OnAlgoParams, StepRule,
                                    default_paper_space, simulate,
                                    simulate_sharded)
            from repro.core.fleet import RawOverlay
            from repro.data.traces import TraceSpec, iid_trace
            from repro.launch.mesh import make_test_mesh

            space = default_paper_space(num_w=4)
            N, T = 16, 150
            trace, _ = iid_trace(space, TraceSpec(T=T, N=N, seed=4))
            tables = space.tables()
            params = OnAlgoParams(B=jnp.full((N,), 0.08),
                                  H=jnp.float32(7e8))
            rule = StepRule.inv_sqrt(0.5)
            rng = np.random.default_rng(1)
            ov = RawOverlay(
                o=jnp.asarray(rng.uniform(0.05, 0.12, (T, N)), jnp.float32),
                h=jnp.asarray(rng.uniform(3e8, 6e8, (T, N)), jnp.float32),
                w=jnp.asarray(rng.uniform(0.0, 0.3, (T, N)), jnp.float32),
                correct_local=jnp.asarray(rng.random((T, N)) < 0.6,
                                          jnp.float32),
                correct_cloud=jnp.asarray(rng.random((T, N)) < 0.85,
                                          jnp.float32))
            s_ref, f_ref = simulate(trace, tables, params, rule,
                                    overlay=ov,
                                    enforce_slot_capacity=True)
            mesh = make_test_mesh((4,), ("data",))
            s_sh, f_sh = simulate_sharded(trace, tables, params, rule,
                                          mesh, overlay=ov,
                                          enforce_slot_capacity=True)
            assert set(s_sh) == set(s_ref)
            for k in s_ref:
                np.testing.assert_allclose(np.asarray(s_sh[k]),
                                           np.asarray(s_ref[k]),
                                           rtol=1e-4, atol=1e-5,
                                           err_msg=k)
            np.testing.assert_allclose(np.asarray(f_sh.lam),
                                       np.asarray(f_ref.lam), rtol=1e-4,
                                       atol=1e-6)
            print("OK")
        """)
        assert "OK" in out

    def test_sharded_stream_matches_single_device(self):
        """simulate_sharded_stream across 4 real shards: per-slab
        generated workload + resumable shard_map scan == the
        single-process scan engine on the materialized horizon."""
        out = run_with_devices("""
            import numpy as np, jax, jax.numpy as jnp
            from repro.core import (OnAlgoParams, StepRule,
                                    default_paper_space, simulate,
                                    simulate_sharded_stream)
            from repro.data.traces import TraceSpec, iid_trace
            from repro.launch.mesh import make_test_mesh

            space = default_paper_space(num_w=4)
            N, T = 16, 150
            trace, _ = iid_trace(space, TraceSpec(T=T, N=N, seed=2))
            tables = space.tables()
            params = OnAlgoParams(B=jnp.full((N,), 0.08),
                                  H=jnp.float32(7e8))
            rule = StepRule.inv_sqrt(0.5)
            series, fin = simulate(trace, tables, params, rule)

            def source(t0, L):  # slab view of the same trace, no overlay
                return trace.j_idx[t0:t0 + L], None

            mesh = make_test_mesh((4,), ("data",))
            s_st, fin_st = simulate_sharded_stream(
                source, T, N, tables, params, rule, mesh, slab=64)
            for k in ("reward", "power", "load", "offloads", "tasks",
                      "mu", "lam_norm"):
                np.testing.assert_allclose(np.asarray(s_st[k]),
                                           np.asarray(series[k]),
                                           rtol=1e-4, atol=1e-5,
                                           err_msg=k)
            np.testing.assert_allclose(np.asarray(fin_st.lam),
                                       np.asarray(fin.lam), rtol=1e-4,
                                       atol=1e-6)
            np.testing.assert_allclose(float(fin_st.mu), float(fin.mu),
                                       rtol=1e-4, atol=1e-7)
            np.testing.assert_array_equal(
                np.asarray(fin_st.rho.counts),
                np.asarray(fin.rho.counts))
            print("OK")
        """, n_devices=4)
        assert "OK" in out

    def test_sharded_topology_matches_single_device(self):
        """Multi-cloudlet duals across 4 real shards: the per-slot
        collective is the psum of each shard's (K,) segment partials —
        the mobility association crosses shard boundaries freely — and
        the series must match the single-process scan engine."""
        out = run_with_devices("""
            import numpy as np, jax, jax.numpy as jnp
            from repro.core import (OnAlgoParams, StepRule,
                                    default_paper_space, simulate,
                                    simulate_sharded)
            from repro.data.traces import TraceSpec, iid_trace
            from repro.launch.mesh import make_test_mesh
            from repro.topology import Topology

            space = default_paper_space(num_w=4)
            N, T = 16, 150
            trace, _ = iid_trace(space, TraceSpec(T=T, N=N, seed=4))
            tables = space.tables()
            params = OnAlgoParams(B=jnp.full((N,), 0.08),
                                  H=jnp.float32(7e8))
            rule = StepRule.inv_sqrt(0.5)
            topo = Topology.mobility_walk(4, N, T, H=params.H,
                                          p_handover=0.1, seed=2)
            s_ref, f_ref = simulate(trace, tables, params, rule,
                                    topology=topo,
                                    enforce_slot_capacity=True)
            mesh = make_test_mesh((4,), ("data",))
            s_sh, f_sh = simulate_sharded(trace, tables, params, rule,
                                          mesh, topology=topo,
                                          enforce_slot_capacity=True)
            assert set(s_sh) == set(s_ref)
            assert s_sh["mu_k"].shape == (T, 4)
            for k in s_ref:
                np.testing.assert_allclose(np.asarray(s_sh[k]),
                                           np.asarray(s_ref[k]),
                                           rtol=1e-4, atol=1e-5,
                                           err_msg=k)
            np.testing.assert_allclose(np.asarray(f_sh.mu),
                                       np.asarray(f_ref.mu), rtol=1e-4,
                                       atol=1e-7)
            print("OK")
        """, n_devices=4)
        assert "OK" in out

    def test_sharded_stream_shard_local_generation(self):
        """simulate_sharded_stream(source_cols=...) across 4 real shards:
        each shard generates ONLY its own workload columns inside the
        shard_map (counter-offset draws), and the end-to-end service
        metrics equal the materialized scan reference."""
        out = run_with_devices("""
            import numpy as np
            from repro.serve.simulator import (SimConfig, simulate_service,
                                               synthetic_pool)
            from repro.serve.compile import compile_service_streaming

            pool = synthetic_pool()
            sim = SimConfig(num_devices=16, T=150, algo="onalgo",
                            B_n=0.06, H=4 * 441e6, seed=4)
            # the column-addressed source really equals full-slab slicing
            cs = compile_service_streaming(sim, pool)
            j_full, ov_full = cs.slab(37, 64)
            j_cols, _ = cs.slab_cols(37, 64, 4, 4)
            np.testing.assert_array_equal(np.asarray(j_cols),
                                          np.asarray(j_full)[:, 4:8])

            ref = simulate_service(sim, pool, engine="scan")
            out = simulate_service(sim, pool, engine="sharded",
                                   materialize=False, slab=64)
            for k in ref:
                assert abs(out[k] - ref[k]) <= 2e-5 * abs(ref[k]) + 1e-5, (
                    k, out[k], ref[k])
            print("OK")
        """, n_devices=4)
        assert "OK" in out

    def test_compressed_psum_across_shards(self):
        out = run_with_devices("""
            import numpy as np, jax, jax.numpy as jnp
            from functools import partial
            from jax.sharding import PartitionSpec as P
            from repro.launch.mesh import make_test_mesh
            from repro.train.compression import compressed_psum, init_residual

            mesh = make_test_mesh((8,), ("data",))
            g = jnp.arange(32, dtype=jnp.float32).reshape(8, 4) / 7.0

            @partial(jax.shard_map, mesh=mesh, in_specs=P("data"),
                     out_specs=(P("data"), P("data")), check_vma=False)
            def run(g_shard):
                grads = {"w": g_shard[0]}
                res = init_residual(grads)
                mean, new_res = compressed_psum(grads, res, "data")
                return mean["w"][None], new_res["w"][None]

            mean, res = run(g)
            want = np.asarray(g).mean(axis=0)
            for i in range(8):
                np.testing.assert_allclose(np.asarray(mean[i]), want,
                                           atol=0.05)
            # error feedback: residual + dequantized == original + residual_in
            print("OK")
        """)
        assert "OK" in out

    def test_sharded_train_step_runs_and_matches_single(self):
        """FSDP+TP sharded train step == single-device step (same loss)."""
        out = run_with_devices("""
            import numpy as np, jax, jax.numpy as jnp
            from repro.configs import get_config
            from repro.models.api import ModelAPI
            from repro.parallel import axis_rules
            from repro.parallel.sharding import shape_aware_spec_tree
            from repro.train import optimizer as opt
            from repro.train.trainer import TrainState, make_train_step
            from repro.launch.mesh import make_test_mesh

            cfg = get_config("olmo_1b").reduced()
            api = ModelAPI(cfg)
            params, logical = api.init(jax.random.PRNGKey(0))
            spec = opt.OptimizerSpec(name="adamw", lr=1e-3)
            state = TrainState.create(params, spec)
            toks = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                                      cfg.vocab_size)
            batch = {"tokens": toks}
            step = make_train_step(api.loss, spec,
                                   opt.cosine_schedule(1e-3, 5, 100))
            ref_state, ref_m = jax.jit(step)(state, batch)

            mesh = make_test_mesh((4, 2), ("data", "model"))
            with axis_rules(mesh=mesh):
                shapes = jax.eval_shape(lambda: params)
                p_sh = shape_aware_spec_tree(shapes, logical, mesh=mesh)
                opt_logical = opt.opt_state_specs(
                    spec, shapes, logical)
                o_sh = shape_aware_spec_tree(
                    jax.eval_shape(lambda: state.opt_state), opt_logical,
                    mesh=mesh)
                from jax.sharding import NamedSharding, PartitionSpec as P
                st_sh = TrainState(params=p_sh, opt_state=o_sh,
                                   step=NamedSharding(mesh, P()))
                b_sh = {"tokens": NamedSharding(mesh, P("data", None))}
                jstep = jax.jit(step, in_shardings=(st_sh, b_sh),
                                out_shardings=(st_sh, None))
                with mesh:
                    new_state, m = jstep(state, batch)
            assert abs(float(m["loss"]) - float(ref_m["loss"])) < 1e-3, (
                float(m["loss"]), float(ref_m["loss"]))
            d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - b.astype(jnp.float32)))),
                new_state.params, ref_state.params)
            assert max(jax.tree.leaves(d)) < 5e-2
            print("OK")
        """)
        assert "OK" in out

    def test_elastic_checkpoint_restore_other_device_count(self):
        """Save on 8 devices, restore on 4 — mesh-independent format."""
        import tempfile
        with tempfile.TemporaryDirectory() as d:
            run_with_devices(f"""
                import jax, jax.numpy as jnp
                from jax.sharding import NamedSharding, PartitionSpec as P
                from repro.launch.mesh import make_test_mesh
                from repro.train.checkpoint import save
                mesh = make_test_mesh((8,), ("data",))
                x = jax.device_put(jnp.arange(64.0),
                                   NamedSharding(mesh, P("data")))
                save({d!r}, 3, {{"x": x}})
                print("SAVED")
            """, n_devices=8)
            out = run_with_devices(f"""
                import numpy as np, jax, jax.numpy as jnp
                from jax.sharding import NamedSharding, PartitionSpec as P
                from repro.launch.mesh import make_test_mesh
                from repro.train.checkpoint import restore
                mesh = make_test_mesh((4,), ("data",))
                sh = {{"x": NamedSharding(mesh, P("data"))}}
                back = restore({d!r}, 3, {{"x": jnp.zeros(64)}},
                               shardings=sh)
                np.testing.assert_array_equal(np.asarray(back["x"]),
                                              np.arange(64.0))
                assert len(back["x"].sharding.device_set) == 4
                print("OK")
            """, n_devices=4)
            assert "OK" in out


class TestPipelineParallel:
    def test_gpipe_matches_sequential(self):
        out = run_with_devices("""
            import numpy as np, jax, jax.numpy as jnp
            from repro.launch.mesh import make_test_mesh
            from repro.parallel.pipeline import pipeline_apply

            # toy 4-layer MLP: y = relu(x W_i) applied in sequence
            S, D = 4, 16   # stages, width
            key = jax.random.PRNGKey(0)
            Ws = jax.random.normal(key, (S, D, D)) * 0.3
            x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, D))  # (mb,b,d)

            def stage_fn(w, h):
                return jax.nn.relu(h @ w)

            # sequential reference over microbatches
            ref = []
            for m in range(8):
                h = x[m]
                for s in range(S):
                    h = stage_fn(Ws[s], h)
                ref.append(h)
            ref = jnp.stack(ref)

            mesh = make_test_mesh((4,), ("pod",))
            out = pipeline_apply(stage_fn, Ws, x, mesh, axis="pod")
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=1e-4, atol=1e-5)
            print("OK")
        """, n_devices=4)
        assert "OK" in out


class TestShardedGateway:
    def test_gateway_tick_on_mesh_matches_single_device(self):
        """The live gateway's jitted tick with mesh-sharded persistent
        state (lam / rho counts over the data axis) reproduces the
        unsharded core's decision stream exactly."""
        out = run_with_devices("""
            import numpy as np, jax
            from repro.launch.mesh import make_test_mesh
            from repro.serve.compile import compile_service_streaming
            from repro.serve.gateway import GatewayCore
            from repro.serve.simulator import SimConfig, synthetic_pool
            from repro.workload.loadgen import ServiceLoadGen

            assert jax.device_count() == 4
            pool = synthetic_pool()
            sim = SimConfig(num_devices=32, T=96, algo="onalgo", seed=4)
            ss = compile_service_streaming(sim, pool)
            mesh = make_test_mesh((4,), ("data",))

            ref = GatewayCore.for_service(ss)
            sh = GatewayCore.for_service(ss, mesh=mesh)
            lg = ServiceLoadGen(ss)
            for wv in lg.waves(0, 96):
                o_r, a_r = ref.tick(wv.idx, wv.o, wv.h, wv.w)
                o_s, a_s = sh.tick(wv.idx, wv.o, wv.h, wv.w)
                assert np.array_equal(o_r, o_s), wv.t
                assert np.array_equal(a_r, a_s), wv.t
            assert np.array_equal(np.asarray(ref.state.lam),
                                  np.asarray(sh.state.lam))
            # the persistent state stayed sharded across 96 donated ticks
            shd = sh.state.lam.sharding
            assert getattr(shd, "spec", None) is not None, shd
            print("OK")
        """, n_devices=4)
        assert "OK" in out

    def test_pipelined_loop_on_mesh_matches_single_device(self):
        """The depth-bounded wave pipeline over a mesh-sharded core —
        warmup compiles included — still replays the unsharded
        sequential core's decision stream bit for bit."""
        out = run_with_devices("""
            import numpy as np, jax
            from repro.launch.mesh import make_test_mesh
            from repro.serve.compile import compile_service_streaming
            from repro.serve.gateway import GatewayCore, run_pipelined_loop
            from repro.serve.simulator import SimConfig, synthetic_pool
            from repro.workload.loadgen import ServiceLoadGen

            assert jax.device_count() == 4
            pool = synthetic_pool()
            sim = SimConfig(num_devices=32, T=96, algo="onalgo", seed=4)
            ss = compile_service_streaming(sim, pool)
            mesh = make_test_mesh((4,), ("data",))

            ref = GatewayCore.for_service(ss)
            lg = ServiceLoadGen(ss)
            offs, adms = [], []
            for wv in lg.waves(0, 96):
                o, a = ref.tick(wv.idx, wv.o, wv.h, wv.w)
                offs.append(o); adms.append(a)

            sh = GatewayCore.for_service(ss, mesh=mesh)
            sh.warmup()  # throwaway state shares the mesh sharding
            replies, stats = run_pipelined_loop(
                sh, ServiceLoadGen(ss), 0, 96, max_in_flight=2,
                slo_ms=60_000.0)
            assert stats.waves == 96 and stats.fallback_waves == 0
            assert stats.overlapped_waves > 0
            for t, r in enumerate(replies):
                assert not r.fallback and r.t == t
                assert np.array_equal(r.offload, offs[t]), t
                assert np.array_equal(r.admitted, adms[t]), t
            assert np.array_equal(np.asarray(ref.state.lam),
                                  np.asarray(sh.state.lam))
            shd = sh.state.lam.sharding
            assert getattr(shd, "spec", None) is not None, shd
            print("OK")
        """, n_devices=4)
        assert "OK" in out
