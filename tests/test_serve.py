"""Serving substrate: engine, batcher, admission controller, simulator,
the golden v0 fixture, and cross-engine parity of the compiled service
(materialized and streaming lowerings)."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.onalgo import OnAlgoParams, StepRule
from repro.core.state_space import StateSpace
from repro.models.api import ModelAPI
from repro.serve.admission import (AdmissionController, flops_per_request,
                                   quantize_states)
from repro.serve.engine import Batcher, ServingEngine
from repro.serve.simulator import (SimConfig, simulate_service,
                                   synthetic_pool)

SERVICE_METRICS = ("accuracy", "offload_frac", "admit_frac",
                   "avg_power_per_dev", "avg_load", "avg_delay_ms",
                   "tasks", "mu_final")
GOLDEN = pathlib.Path(__file__).parent / "golden" / "service_legacy_fig5.json"



class TestEngine:
    def test_generate_greedy_deterministic(self):
        cfg = get_config("olmo_1b").reduced()
        api = ModelAPI(cfg)
        params, _ = api.init(jax.random.PRNGKey(0))
        eng = ServingEngine(cfg, params, max_len=64)
        prompts = np.asarray(jax.random.randint(
            jax.random.PRNGKey(1), (3, 8), 0, cfg.vocab_size))
        out1 = np.asarray(eng.generate(prompts, steps=6))
        out2 = np.asarray(eng.generate(prompts, steps=6))
        assert out1.shape == (3, 6)
        np.testing.assert_array_equal(out1, out2)
        assert eng.stats.decode_calls == 12

    def test_generate_matches_unbatched(self):
        """Batch composition must not change greedy outputs (dropless MoE
        guarantees this even for MoE archs)."""
        import dataclasses
        cfg = dataclasses.replace(get_config("olmoe_1b_7b").reduced(),
                                  moe_impl="dropless")
        api = ModelAPI(cfg)
        params, _ = api.init(jax.random.PRNGKey(0))
        eng = ServingEngine(cfg, params, max_len=32)
        prompts = np.asarray(jax.random.randint(
            jax.random.PRNGKey(2), (2, 8), 0, cfg.vocab_size))
        batched = np.asarray(eng.generate(prompts, steps=4))
        singles = [np.asarray(eng.generate(prompts[i:i + 1], steps=4))[0]
                   for i in range(2)]
        np.testing.assert_array_equal(batched, np.stack(singles))


class TestBatcher:
    def test_wave_formation_and_padding(self):
        b = Batcher(max_batch=4, buckets=(8, 16))
        for i in range(6):
            b.submit(list(range(i + 1)))
        w1 = b.next_wave()
        assert len(w1) == 4 and len(b) == 2
        assert b.bucket_len(5) == 8 and b.bucket_len(9) == 16
        padded = Batcher.pad_tokens(w1, 8)
        assert padded.shape == (4, 8)
        assert padded[0, 1] == 0  # padding
        w2 = b.next_wave()
        assert len(w2) == 2 and b.next_wave() is None


class TestAdmission:
    def _ctrl(self, N=8, H=2.0, B=0.5):
        space = StateSpace(o_levels=(0.2, 0.5, 0.9),
                           h_levels=(0.5, 1.0, 1.5),
                           w_levels=(0.0, 0.1, 0.2, 0.3))
        params = OnAlgoParams(B=jnp.full((N,), B), H=jnp.float32(H))
        return AdmissionController(space, params, StepRule.inv_sqrt(0.5), N)

    def test_congestion_price_rises_under_overload(self):
        N = 8
        ctrl = self._ctrl(N=N, H=0.5)  # tiny capacity
        rng = np.random.default_rng(0)
        for _ in range(300):
            ctrl.admit(o=np.full(N, 0.2), h=np.full(N, 1.0),
                       w=rng.uniform(0.2, 0.3, N),
                       task_mask=np.ones(N, bool))
        assert ctrl.mu > 0  # capacity dual engaged

    def test_no_offload_when_no_gain(self):
        N = 4
        ctrl = self._ctrl(N=N)
        off = ctrl.admit(o=np.full(N, 0.2), h=np.full(N, 1.0),
                         w=np.zeros(N), task_mask=np.ones(N, bool))
        assert not off.any()

    def test_flops_cost_scales_with_arch(self):
        small = flops_per_request(get_config("olmo_1b"), 1024)
        big = flops_per_request(get_config("deepseek_67b"), 1024)
        assert big > 20 * small
        # MoE: active params only
        moe = get_config("olmoe_1b_7b")
        assert (flops_per_request(moe, 1024)
                < 2.0 * moe.param_count() * 1024)


def _golden():
    return json.loads(GOLDEN.read_text())


def _sim_from_entry(entry) -> SimConfig:
    return SimConfig(**entry["sim"])


class TestGoldenFixture:
    """RNG contract v0 stays pinned by tests/golden/service_legacy_fig5.json.

    The legacy Python loop (and the product's v0 compile path) are gone;
    the frozen sampler in tests/legacy_workload.py replays the exact v0
    draws through the public fleet engine + metrics fold, which is what
    the fixture regression-checks for every policy."""

    @pytest.fixture(scope="class")
    def golden(self):
        return _golden()

    @pytest.fixture(scope="class")
    def pool(self):
        g = _golden()
        return synthetic_pool(**g["pool"])

    def test_fixture_covers_all_policies(self, golden):
        assert {"onalgo", "ato", "rco", "ocos", "local", "cloud",
                "onalgo_zeta300"} <= set(golden["entries"])

    @pytest.mark.parametrize("name", ["onalgo", "ato", "rco", "ocos",
                                      "local", "cloud", "onalgo_zeta300"])
    def test_frozen_v0_replay_matches_golden(self, golden, pool, name):
        """rel=5e-3: the engine prices decisions in float32 while the
        original loop used float64, so over T=2000 slots a handful of
        near-threshold offload/admit decisions flip (max observed metric
        deviation 7e-4).  Contract regressions are O(1), far outside."""
        from legacy_workload import replay_golden
        entry = golden["entries"][name]
        out = replay_golden(_sim_from_entry(entry), pool)
        for k in SERVICE_METRICS:
            assert out[k] == pytest.approx(entry["metrics"][k], rel=5e-3,
                                           abs=1e-6), k

    def test_v0_contract_retired(self):
        with pytest.raises(ValueError, match="retired"):
            simulate_service(SimConfig(num_devices=2, T=40, rng_version=0),
                             synthetic_pool())

    def test_unknown_rng_version_rejected(self):
        with pytest.raises(ValueError, match="rng_version"):
            simulate_service(SimConfig(num_devices=2, T=40, rng_version=7),
                             synthetic_pool())


class TestServiceEngines:
    """simulate_service(engine=...) — identical metrics on the same
    compiled workload across scan / chunked / tiled / sharded, including
    non-divisible N (5) and T (203)."""

    @pytest.fixture(scope="class")
    def pool(self):
        return synthetic_pool()

    @pytest.mark.parametrize("algo", ["onalgo", "local", "cloud"])
    def test_engines_agree(self, pool, algo):
        sim = SimConfig(num_devices=5, T=203, algo=algo, B_n=0.06,
                        H=1.5 * 441e6, seed=4)
        ref = simulate_service(sim, pool, engine="scan")
        runs = {
            "chunked": simulate_service(sim, pool, engine="chunked",
                                        chunk=8),
            "tiled": simulate_service(sim, pool, engine="chunked",
                                      chunk=8, block_n=8),
            "sharded": simulate_service(sim, pool, engine="sharded"),
        }
        for eng, out in runs.items():
            assert set(out) == set(ref)
            for k in SERVICE_METRICS:
                assert out[k] == pytest.approx(ref[k], rel=2e-5,
                                               abs=1e-5), (eng, k)

    def test_chunked_rejects_stateful_baselines(self, pool):
        sim = SimConfig(num_devices=4, T=64, algo="ato")
        with pytest.raises(ValueError, match="chunked"):
            simulate_service(sim, pool, engine="chunked")

    def test_unknown_engine_rejected(self, pool):
        with pytest.raises(ValueError, match="engine"):
            simulate_service(SimConfig(num_devices=4, T=64), pool,
                             engine="warp")


class TestStreamingService:
    """materialize=False: workload slabs generated on device inside the
    engine loop — metrics must be IDENTICAL to the materialized path,
    including non-divisible N (5) / T (203) and slab/chunk misalignment."""

    @pytest.fixture(scope="class")
    def pool(self):
        return synthetic_pool()

    @pytest.mark.parametrize("algo", ["onalgo", "local", "cloud"])
    def test_streaming_chunked_equals_materialized(self, pool, algo):
        sim = SimConfig(num_devices=5, T=203, algo=algo, B_n=0.06,
                        H=1.5 * 441e6, seed=4)
        ref = simulate_service(sim, pool, engine="chunked", chunk=8)
        out = simulate_service(sim, pool, engine="chunked", chunk=8,
                               materialize=False, slab=64)
        for k in SERVICE_METRICS:
            assert out[k] == ref[k], k  # bit-identical, not approx

    def test_streaming_tiled_and_sharded_match_scan(self, pool):
        sim = SimConfig(num_devices=6, T=203, algo="onalgo", B_n=0.06,
                        H=1.5 * 441e6, seed=4)
        ref = simulate_service(sim, pool, engine="scan")
        runs = {
            "tiled": simulate_service(sim, pool, engine="chunked",
                                      chunk=8, block_n=8,
                                      materialize=False, slab=64),
            "sharded": simulate_service(sim, pool, engine="sharded",
                                        materialize=False, slab=80),
        }
        for eng, out in runs.items():
            for k in SERVICE_METRICS:
                assert out[k] == pytest.approx(ref[k], rel=2e-5,
                                               abs=1e-5), (eng, k)

    def test_streaming_default_slab(self, pool):
        """The default slab (16 * chunk) walks a T that is neither a
        slab nor a chunk multiple."""
        sim = SimConfig(num_devices=4, T=275, algo="onalgo", seed=9)
        ref = simulate_service(sim, pool, engine="chunked", chunk=16)
        out = simulate_service(sim, pool, engine="chunked", chunk=16,
                               materialize=False)
        for k in SERVICE_METRICS:
            assert out[k] == ref[k], k

    def test_streaming_rejects_scan_engine(self, pool):
        with pytest.raises(ValueError, match="materialize"):
            simulate_service(SimConfig(num_devices=4, T=64), pool,
                             engine="scan", materialize=False)

    def test_streaming_rejects_arrival_override(self, pool):
        with pytest.raises(ValueError, match="materialize"):
            simulate_service(SimConfig(num_devices=4, T=64), pool,
                             on=np.ones((64, 4), bool), engine="chunked",
                             materialize=False)

    def test_streaming_slab_equals_materialized_compile(self, pool):
        """The streaming lowering's slabs are bit-identical slices of
        compile_service's trace/overlay arrays."""
        from repro.serve.compile import (compile_service,
                                         compile_service_streaming)
        sim = SimConfig(num_devices=5, T=203, algo="onalgo", seed=11)
        mat = compile_service(sim, pool)
        cs = compile_service_streaming(sim, pool)
        for t0, L in ((0, 203), (37, 64), (160, 43)):
            j, ov = cs.slab(t0, L)
            np.testing.assert_array_equal(
                np.asarray(j), np.asarray(mat.trace.j_idx)[t0:t0 + L])
            for f in ("o", "h", "w", "correct_local", "correct_cloud"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(ov, f)),
                    np.asarray(getattr(mat.overlay, f))[t0:t0 + L],
                    err_msg=f"{f} at t0={t0}")

    def test_autotune_picks_runnable_config(self, pool):
        """fleet.autotune on the streaming service source returns a
        candidate whose full run reproduces the scan metrics."""
        from repro.core import fleet
        from repro.serve.compile import compile_service_streaming
        sim = SimConfig(num_devices=4, T=160, algo="onalgo", seed=2)
        cs = compile_service_streaming(sim, pool)
        tune = fleet.autotune(cs.tables, cs.params, cs.rule,
                              source=cs.slab, T=sim.T, N=4,
                              chunks=(8, 16), block_ns=(None, 8),
                              probe_slots=48, repeats=1)
        assert (tune.chunk, tune.block_n) in tune.timings
        assert len(tune.timings) == 4
        assert tune.seconds == tune.timings[(tune.chunk, tune.block_n)]
        ref = simulate_service(sim, pool, engine="scan")
        out = simulate_service(sim, pool, engine="chunked",
                               materialize=False, **tune.kwargs)
        for k in SERVICE_METRICS:
            assert out[k] == pytest.approx(ref[k], rel=2e-5, abs=1e-5), k

    def test_pipelined_equals_sequential_service(self, pool):
        """The pipelined streaming runtime (fused launches, donated
        carries, device-resident series buffers) is bit-identical to
        the sequential slab walk on both streaming engines."""
        sim = SimConfig(num_devices=5, T=203, algo="onalgo", B_n=0.06,
                        H=1.5 * 441e6, seed=4)
        for eng in ("chunked", "sharded"):
            ref = simulate_service(sim, pool, engine=eng, chunk=8,
                                   materialize=False, slab=64,
                                   pipelined=False)
            out = simulate_service(sim, pool, engine=eng, chunk=8,
                                   materialize=False, slab=64,
                                   pipelined=True)
            for k in SERVICE_METRICS:
                assert out[k] == ref[k], (eng, k)  # bitwise, not approx

    def test_slab_aligned_equals_slab(self, pool):
        """The block-aligned slab source (one fewer covering uniform
        block generated per slab) is bit-identical to the general one
        at every ROW_BLOCK-aligned start."""
        from repro.serve.compile import compile_service_streaming
        sim = SimConfig(num_devices=5, T=203, algo="onalgo", seed=11)
        cs = compile_service_streaming(sim, pool)
        for t0, L in ((0, 64), (64, 64), (128, 75), (64, 40)):
            j_a, ov_a = cs.slab_aligned(t0, L)
            j, ov = cs.slab(t0, L)
            np.testing.assert_array_equal(np.asarray(j_a), np.asarray(j))
            for f in ("o", "h", "w", "correct_local", "correct_cloud"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(ov_a, f)),
                    np.asarray(getattr(ov, f)),
                    err_msg=f"{f} at t0={t0}")

    def test_autotune_slab_search(self, pool):
        """slabs= joins the autotune search space (pipelined runtime):
        keys grow a slab coordinate, the winner rides AutotuneResult,
        and its kwargs reproduce the scan metrics."""
        from repro.core import fleet
        from repro.serve.compile import compile_service_streaming
        sim = SimConfig(num_devices=4, T=160, algo="onalgo", seed=2)
        cs = compile_service_streaming(sim, pool)
        tune = fleet.autotune(cs.tables, cs.params, cs.rule,
                              source=cs.slab, T=sim.T, N=4,
                              chunks=(8, 16), block_ns=(None,),
                              slabs=(64, 128), pipelined=True,
                              probe_slots=128, repeats=1)
        assert tune.slab in (64, 128)
        assert len(tune.timings) == 4  # 2 chunks x 1 block_n x 2 slabs
        assert all(len(k) == 3 for k in tune.timings)  # (..., slab) keys
        assert tune.kwargs["slab"] == tune.slab
        ref = simulate_service(sim, pool, engine="scan")
        out = simulate_service(sim, pool, engine="chunked",
                               materialize=False, pipelined=True,
                               **tune.kwargs)
        for k in SERVICE_METRICS:
            assert out[k] == pytest.approx(ref[k], rel=2e-5, abs=1e-5), k


class TestServiceWorkloads:
    def test_scenario_arrivals_drive_batched_service(self):
        """A composed fleet scenario replays through the service tier on
        every engine, and the arrivals actually gate the workload."""
        from repro.scenarios import Scenario, compile_scenario
        c = compile_scenario(
            Scenario("churn_outage", T=120, N=4, seed=6).with_extra(
                churn_frac=0.3, n_outages=1, outage_len=30))
        mask = c.task_mask()
        pool = synthetic_pool(seed=2)
        sim = SimConfig(num_devices=4, T=120, algo="onalgo", seed=7)
        out = simulate_service(sim, pool, on=mask)
        assert out["tasks"] == mask.sum()
        chunked = simulate_service(sim, pool, on=mask, engine="chunked",
                                   chunk=8)
        for k in SERVICE_METRICS:
            assert chunked[k] == pytest.approx(out[k], rel=2e-5,
                                               abs=1e-5), k

    def test_arrival_override_keeps_other_streams(self):
        """Overriding arrivals must not perturb the image/channel draws:
        counter addressing has no draw-order coupling (unlike v0, where
        skipping the arrival draws shifted every later draw)."""
        from repro.serve.compile import compile_service
        pool = synthetic_pool(seed=2)
        sim = SimConfig(num_devices=4, T=160, algo="onalgo", seed=9)
        cs_default = compile_service(sim, pool)
        cs_forced = compile_service(
            sim, pool, on=np.ones((sim.T, sim.num_devices), bool))
        # raw value streams are identical; only the task gating differs
        for field in ("o", "h", "w", "correct_local", "correct_cloud"):
            np.testing.assert_array_equal(
                np.asarray(getattr(cs_default.overlay, field)),
                np.asarray(getattr(cs_forced.overlay, field)), err_msg=field)
        assert cs_forced.on.all()
        assert not cs_default.on.all()

    def test_quantize_vectorized_matches_numpy(self):
        """The fused jitted quantizer == the numpy argmin it replaced
        (away from float32-ulp level-midpoint ties, where the old float64
        path could differ), for one-slot (N,) and horizon (T, N) batches."""
        space = StateSpace(o_levels=(0.2, 0.5, 0.9),
                           h_levels=(0.5, 1.0, 1.5),
                           w_levels=(0.0, 0.1, 0.2, 0.3))
        rng = np.random.default_rng(0)
        o = rng.uniform(0.0, 1.1, (40, 6))
        h = rng.uniform(0.0, 2.0, (40, 6))
        w = rng.uniform(0.0, 0.4, (40, 6))
        task = rng.random((40, 6)) < 0.7

        def legacy(o, h, w, task):
            lv = lambda name: np.asarray(getattr(space, name))
            io = np.abs(o[:, None] - lv("o_levels")).argmin(-1)
            ih = np.abs(h[:, None] - lv("h_levels")).argmin(-1)
            iw = np.abs(w[:, None] - lv("w_levels")).argmin(-1)
            j = np.asarray(space.encode(io, ih, iw))
            return np.where(task, j, 0).astype(np.int32)

        want = np.stack([legacy(o[t], h[t], w[t], task[t])
                         for t in range(40)])
        np.testing.assert_array_equal(
            quantize_states(space, o, h, w, task), want)
        np.testing.assert_array_equal(
            quantize_states(space, o[0], h[0], w[0], task[0]), want[0])

    @pytest.mark.parametrize("shape", [(64, 300), (3, 1)])
    @pytest.mark.parametrize("S", [1, 3, 64, 128, 129, 512])
    def test_lookup_equals_gather_bit_for_bit(self, S, shape):
        """The value lowering folds each (S,) table per entry and looks
        it up (a select tree for S <= 128, the gather above): its
        outputs equal gathering the raw tables with ``jnp.take`` and
        folding each element, as bits, on tables holding -0.0,
        subnormals and values at a level midpoint; and ``j`` equals
        ``quantize_states_device`` of the gathered values for every
        index in [0, S)."""
        import types

        from repro.core.onalgo import risk_adjusted_gain
        from repro.serve.admission import quantize_states_device
        from repro.serve.compile import _lower_values
        from repro.serve.simulator import RATES, power_of_rate

        space = StateSpace(o_levels=tuple(power_of_rate(RATES).tolist()),
                           h_levels=(351e6, 441e6, 531e6),
                           w_levels=tuple(np.linspace(0, 0.3, 8).tolist()))
        rng = np.random.default_rng(S)
        f32 = np.float32
        specials = np.array([-0.0, 1e-40, -1e-40, 0.0], f32)

        def table(lo, hi, mids=()):
            t = rng.uniform(lo, hi, S).astype(f32)
            extra = np.concatenate([specials, np.asarray(mids, f32)])
            at = rng.permutation(S)[:min(S, len(extra))]
            t[at] = extra[:len(at)]
            return t

        w_lv = np.asarray(space.w_levels, f32)
        h_lv = np.asarray(space.h_levels, f32)
        o_lv = np.asarray(space.o_levels, f32)
        mid = lambda lv: (lv[:-1] + lv[1:]) / f32(2)
        v_risk, zeta_pen = f32(0.5), f32(2.0**-7)
        cycles = table(150e6, 700e6, mid(h_lv))
        # phi = midpoint + zeta_pen with sigma = 0 folds onto the midpoint
        phi = table(0.0, 0.4, mid(w_lv) + zeta_pen)
        sigma = table(0.0, 0.1)
        sigma[np.isin(phi, mid(w_lv) + zeta_pen)] = 0.0
        tabs = tuple(jnp.asarray(t) for t in (
            cycles, phi, sigma, table(0.3, 1.0), table(0.0, 1.0),
            table(0.0, 1.0)))  # cycles, phi, sigma, d_local, cl, cc
        o_levels = jnp.asarray([o_lv[0], mid(o_lv)[1], o_lv[2]], f32)

        def lowered(img, rates, on):
            wl = types.SimpleNamespace(img=img, rates=rates, on=on)
            return _lower_values(wl, space, None, o_levels, tabs[0],
                                 tabs[1], tabs[2], tabs[3], tabs[4],
                                 tabs[5], v_risk, zeta_pen)

        def per_element(img, rates, on):
            cyc, ph, sg, dl, cl, cc = (jnp.take(t, img) for t in tabs)
            o = jnp.take(o_levels, rates)
            w = jnp.clip(risk_adjusted_gain(ph, sg, v_risk) - zeta_pen,
                         0.0, 1.0)
            j = quantize_states_device(space, o, cyc, w, on)
            return on, j, o, cyc, w, cl, cc, dl

        img = rng.integers(0, S, shape).astype(np.int32)
        rates = rng.integers(0, len(RATES), shape).astype(np.int32)
        on = rng.random(shape) < 0.6
        every = np.arange(S, dtype=np.int32)[None]
        for args in ((img, rates, on),
                     (every, every % len(RATES), np.ones_like(every, bool))):
            got = jax.jit(lowered)(*args)
            want = jax.jit(per_element)(*args)
            for name, g, w in zip(("on", "j", "o", "h", "w", "cl", "cc",
                                   "d_local"), got, want):
                g, w = np.asarray(g), np.asarray(w)
                assert g.dtype == w.dtype and g.shape == w.shape, name
                np.testing.assert_array_equal(
                    g.view(np.uint32) if g.dtype == np.float32 else g,
                    w.view(np.uint32) if w.dtype == np.float32 else w,
                    err_msg=name)


@pytest.mark.slow
class TestSimulator:
    @pytest.fixture(scope="class")
    def pool(self):
        from repro.serve.simulator import make_scenario
        _, pair, _, pool = make_scenario("hard", seed=0)
        return pair, pool

    def test_policy_ordering(self, pool):
        from repro.serve.simulator import SimConfig, simulate_service
        pair, pool = pool
        res = {}
        for algo in ["local", "onalgo", "ocos"]:
            res[algo] = simulate_service(
                SimConfig(num_devices=4, T=800, algo=algo, B_n=0.06,
                          H=2 * 441e6, seed=1), pool)
        # offloading beats local-only on accuracy
        assert res["onalgo"]["accuracy"] > res["local"]["accuracy"] + 0.02
        # OnAlgo spends far less power than always-offload
        assert (res["onalgo"]["avg_power_per_dev"]
                < 0.6 * res["ocos"]["avg_power_per_dev"])
        # and stays within a stone's throw of its accuracy
        assert res["onalgo"]["accuracy"] > res["ocos"]["accuracy"] - 0.03

    def test_power_budget_respected(self, pool):
        from repro.serve.simulator import SimConfig, simulate_service
        pair, pool = pool
        out = simulate_service(SimConfig(num_devices=4, T=1500,
                                         algo="onalgo", B_n=0.05,
                                         H=2 * 441e6, seed=2), pool)
        assert out["avg_power_per_dev"] <= 0.05 * 1.15

    def test_delay_extension_reduces_offloads(self, pool):
        from repro.serve.simulator import SimConfig, simulate_service
        pair, pool = pool
        base = simulate_service(SimConfig(num_devices=4, T=600,
                                          algo="onalgo", seed=3), pool)
        lazy = simulate_service(SimConfig(num_devices=4, T=600,
                                          algo="onalgo", seed=3,
                                          zeta=800.0), pool)
        assert lazy["offload_frac"] <= base["offload_frac"] + 1e-9
