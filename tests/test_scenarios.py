"""Scenario engine: registry round-trips, contract compliance, chunked
Pallas kernel parity, and vmapped-sweep vs loop equivalence."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import OnAlgoParams, StepRule, default_paper_space, simulate
from repro.core.fleet import simulate_chunked
from repro.data.traces import TraceSpec, iid_trace
from repro.kernels.onalgo_step import onalgo_chunked_pallas
from repro.kernels.ref import onalgo_chunked_ref
from repro.scenarios import (MODIFIERS, Scenario, compile_scenario, compose,
                             default_scenarios, grid_from_cells, names,
                             product_grid, run_scenario, stack_params,
                             sweep_simulate, unstack_series)

RULE = StepRule.inv_sqrt(0.5)


def _small(sc: Scenario) -> Scenario:
    return dataclasses.replace(sc, T=240, N=6)


class TestRegistry:
    def test_all_kinds_have_defaults(self):
        assert set(names()) == {sc.kind for sc in default_scenarios()}

    @pytest.mark.parametrize("sc", default_scenarios(),
                             ids=lambda sc: sc.kind)
    def test_spec_round_trips(self, sc):
        d = sc.to_dict()
        assert Scenario.from_dict(d) == sc
        # dicts are plain data: survive a JSON hop
        import json
        assert Scenario.from_dict(json.loads(json.dumps(d))) == sc

    @pytest.mark.parametrize("sc", default_scenarios(),
                             ids=lambda sc: sc.kind)
    def test_compiles_to_core_contract(self, sc):
        sc = _small(sc)
        c = compile_scenario(sc)
        T, N = c.trace.j_idx.shape
        assert (T, N) == (sc.T, sc.N)
        o, h, w = c.tables
        assert o.shape[-1] == c.M and h.shape[-1] == c.M
        assert c.params.B.shape == (N,)
        j = np.asarray(c.trace.j_idx)
        assert j.min() >= 0 and j.max() < c.M
        # and fleet.simulate consumes it unchanged
        series, final, _ = run_scenario(c, rule=RULE, engine="scan",
                                        use_kernel=False)
        assert series["reward"].shape == (sc.T,)
        assert np.all(np.asarray(series["offloads"])
                      <= np.asarray(series["tasks"]))

    def test_unknown_kind_raises(self):
        with pytest.raises(KeyError):
            compile_scenario(Scenario("no_such_kind"))


class TestScenarioSemantics:
    def test_churn_masks_absent_devices(self):
        sc = Scenario("churn", T=300, N=6, seed=1).with_extra(churn_frac=0.5)
        c = compile_scenario(sc)
        j = np.asarray(c.trace.j_idx)
        arrive, depart = c.meta["arrive"], c.meta["depart"]
        slots = np.arange(sc.T)[:, None]
        outside = (slots < arrive[None, :]) | (slots >= depart[None, :])
        assert np.all(j[outside] == 0)
        assert j[~outside].max() > 0

    def test_flash_crowd_spikes_load(self):
        sc = Scenario("flash_crowd", T=400, N=8, seed=2,
                      task_prob=0.3).with_extra(n_events=2, event_len=50)
        c = compile_scenario(sc)
        j = np.asarray(c.trace.j_idx)
        in_event = np.zeros(sc.T, bool)
        for s in c.meta["event_starts"]:
            in_event[s:s + c.meta["event_len"]] = True
        assert (j[in_event] > 0).mean() > (j[~in_event] > 0).mean() + 0.3

    def test_outage_blocks_offloading(self):
        sc = Scenario("outage", T=400, N=6, seed=3).with_extra(
            n_outages=2, outage_len=80)
        c = compile_scenario(sc)
        assert c.M == 2 * default_paper_space(num_w=sc.num_w).M
        series, _, _ = run_scenario(c, rule=RULE, engine="scan",
                                    use_kernel=False)
        off = np.asarray(series["offloads"])
        down = c.meta["down"]
        assert off[down].sum() == 0
        assert off[~down].sum() > 0

    def test_heterogeneous_tables_are_per_device(self):
        c = compile_scenario(_small(Scenario("heterogeneous", seed=4)))
        o, h, w = c.tables
        assert o.shape == (6, c.M) and w.shape == (6, c.M)
        # per-device power scales actually differ across the fleet
        col = np.asarray(o[:, 1])
        assert np.unique(col).size > 1
        # null state stays free for every device
        assert np.all(np.asarray(o[:, 0]) == 0)

    def test_diurnal_traffic_oscillates(self):
        sc = Scenario("diurnal", T=800, N=16, seed=5).with_extra(
            period=200, amp=0.9)
        c = compile_scenario(sc)
        tasks = (np.asarray(c.trace.j_idx) > 0).mean(axis=1)
        # average task rate near the cycle peaks vs troughs must differ
        phase = np.sin(2 * np.pi * np.arange(sc.T) / 200)
        assert tasks[phase > 0.7].mean() > tasks[phase < -0.7].mean() + 0.2

    def test_task_mask_feeds_serve_simulator(self):
        c = compile_scenario(Scenario("flash_crowd", T=120, N=4, seed=6))
        mask = c.task_mask()
        assert mask.shape == (120, 4) and mask.dtype == bool
        assert mask.sum() > 0


class TestChunkedKernel:
    @pytest.mark.parametrize("N,M,T,chunk", [
        (8, 16, 64, 8), (24, 37, 96, 16), (64, 73, 40, 8)])
    def test_matches_ref_random_fleet(self, N, M, T, chunk):
        ks = jax.random.split(jax.random.PRNGKey(N + M), 6)
        j = jax.random.randint(ks[0], (T, N), 0, M)
        o = jax.random.uniform(ks[1], (M,))
        h = jax.random.uniform(ks[2], (M,))
        w = jax.random.uniform(ks[3], (M,)) - 0.2
        B = jax.random.uniform(ks[4], (N,)) + 0.05
        lam0 = jax.random.uniform(ks[5], (N,)) * 0.1
        args = (j, lam0, jnp.float32(0.05), jnp.zeros((N, M)), o, h, w, B,
                jnp.float32(2.0), 0.4, 0.5)
        off_k, mu_k, ln_k, lam_k, mufin_k, cnt_k = onalgo_chunked_pallas(
            *args, chunk=chunk, interpret=True)
        off_r, mu_r, ln_r, lam_r, mufin_r, cnt_r = onalgo_chunked_ref(*args)
        np.testing.assert_array_equal(np.asarray(off_k), np.asarray(off_r))
        np.testing.assert_allclose(np.asarray(mu_k), np.asarray(mu_r),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(lam_k), np.asarray(lam_r),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(ln_k), np.asarray(ln_r),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(cnt_k), np.asarray(cnt_r))
        assert float(mufin_k) == pytest.approx(float(mufin_r), rel=1e-5)

    def test_per_device_tables(self):
        N, M, T = 16, 37, 48
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        j = jax.random.randint(ks[0], (T, N), 0, M)
        o = jax.random.uniform(ks[1], (N, M))
        h = jax.random.uniform(ks[2], (N, M))
        w = jax.random.uniform(ks[3], (N, M)) - 0.2
        B = jax.random.uniform(ks[4], (N,)) + 0.05
        args = (j, jnp.zeros((N,)), jnp.float32(0.0), jnp.zeros((N, M)),
                o, h, w, B, jnp.float32(3.0), 0.5, 0.5)
        out_k = onalgo_chunked_pallas(*args, chunk=8, interpret=True)
        out_r = onalgo_chunked_ref(*args)
        np.testing.assert_array_equal(np.asarray(out_k[0]),
                                      np.asarray(out_r[0]))
        np.testing.assert_allclose(np.asarray(out_k[3]),
                                   np.asarray(out_r[3]), rtol=1e-5,
                                   atol=1e-6)

    def test_simulate_chunked_matches_jnp_simulate(self):
        """The full chunked engine == fleet.simulate, series + final state,
        including a non-divisible tail (T % chunk != 0)."""
        space = default_paper_space(num_w=4)
        trace, _ = iid_trace(space, TraceSpec(T=203, N=16, seed=7))
        tables = space.tables()
        params = OnAlgoParams(B=jnp.full((16,), 0.08), H=jnp.float32(7e8))
        s1, f1 = simulate(trace, tables, params, RULE)
        s2, f2 = simulate_chunked(trace, tables, params, RULE, chunk=8)
        assert set(s1) == set(s2)
        for k in s1:
            np.testing.assert_allclose(np.asarray(s1[k]), np.asarray(s2[k]),
                                       rtol=2e-5, atol=1e-5, err_msg=k)
        np.testing.assert_allclose(np.asarray(f1.lam), np.asarray(f2.lam),
                                   rtol=1e-4, atol=1e-6)
        assert float(f1.mu) == pytest.approx(float(f2.mu), abs=1e-5)
        np.testing.assert_array_equal(np.asarray(f1.rho.counts),
                                      np.asarray(f2.rho.counts))

    @pytest.mark.parametrize("kind", ["heterogeneous", "outage", "churn"])
    def test_chunked_engine_on_scenarios(self, kind):
        c = compile_scenario(Scenario(kind, T=240, N=8, seed=9))
        s1, f1, _ = run_scenario(c, rule=RULE, engine="scan",
                                 use_kernel=False)
        s2, f2, _ = run_scenario(c, rule=RULE, engine="chunked", chunk=8)
        for k in ("reward", "power", "load", "offloads", "tasks", "mu"):
            np.testing.assert_allclose(np.asarray(s1[k]), np.asarray(s2[k]),
                                       rtol=2e-5, atol=1e-5, err_msg=k)
        np.testing.assert_allclose(np.asarray(f1.lam), np.asarray(f2.lam),
                                   rtol=1e-4, atol=1e-6)

    def test_horizon_shorter_than_chunk(self):
        """T < chunk must fall back to the jnp tail, not crash on a
        zero-iteration kernel grid."""
        space = default_paper_space(num_w=4)
        trace, _ = iid_trace(space, TraceSpec(T=5, N=8, seed=8))
        tables = space.tables()
        params = OnAlgoParams(B=jnp.full((8,), 0.08), H=jnp.float32(4e8))
        s1, f1 = simulate(trace, tables, params, RULE)
        s2, f2 = simulate_chunked(trace, tables, params, RULE, chunk=8)
        for k in s1:
            np.testing.assert_allclose(np.asarray(s1[k]), np.asarray(s2[k]),
                                       rtol=2e-5, atol=1e-6, err_msg=k)
        np.testing.assert_allclose(np.asarray(f1.lam), np.asarray(f2.lam),
                                   rtol=1e-5, atol=1e-7)

    def test_tiled_engine_matches_scan_nondivisible(self):
        """simulate_chunked(block_n=...) == simulate for N not a tile
        multiple AND T not a chunk multiple (jnp tail + padded tail tile)."""
        space = default_paper_space(num_w=4)
        trace, _ = iid_trace(space, TraceSpec(T=203, N=20, seed=7))
        tables = space.tables()
        params = OnAlgoParams(B=jnp.full((20,), 0.08), H=jnp.float32(9e8))
        s1, f1 = simulate(trace, tables, params, RULE)
        s2, f2 = simulate_chunked(trace, tables, params, RULE, chunk=8,
                                  block_n=8)
        assert set(s1) == set(s2)
        for k in s1:
            np.testing.assert_allclose(np.asarray(s1[k]), np.asarray(s2[k]),
                                       rtol=2e-5, atol=1e-5, err_msg=k)
        np.testing.assert_allclose(np.asarray(f1.lam), np.asarray(f2.lam),
                                   rtol=1e-4, atol=1e-6)
        assert float(f1.mu) == pytest.approx(float(f2.mu), abs=1e-5)
        np.testing.assert_array_equal(np.asarray(f1.rho.counts),
                                      np.asarray(f2.rho.counts))

    def test_collect_decisions_matches_scan(self):
        """simulate_chunked(collect_decisions=True) gives the scan's
        decision matrices, and the scan's offload_margin is positive
        exactly where a device with a task and w > 0 offloads."""
        space = default_paper_space(num_w=4)
        trace, _ = iid_trace(space, TraceSpec(T=64, N=20, seed=5))
        tables = space.tables()
        params = OnAlgoParams(B=jnp.full((20,), 0.08), H=jnp.float32(6e8))
        s1, _ = simulate(trace, tables, params, RULE,
                         enforce_slot_capacity=True, collect_decisions=True)
        s2, _ = simulate_chunked(trace, tables, params, RULE, chunk=8,
                                 block_n=8, enforce_slot_capacity=True,
                                 collect_decisions=True)
        for k in ("offload_mask", "admit_mask"):
            np.testing.assert_array_equal(np.asarray(s1[k]),
                                          np.asarray(s2[k]), err_msg=k)
        j = np.asarray(trace.j_idx)
        w_now = np.asarray(tables[2])[j]
        live = (w_now > 0) & (j > 0)
        off = np.asarray(s1["offload_mask"])
        assert off.any() and (live & ~off).any()
        np.testing.assert_array_equal(
            off, (np.asarray(s1["offload_margin"]) > 0) & live)

    @pytest.mark.parametrize("K", [None, 3])
    def test_resumed_slot_walk_matches_one_run(self, K):
        """simulate_chunked resumed one slot at a time (t0 / state0)
        ends in the one-run state, slot by slot in its series, bit for
        bit — with and without a time-varying topology."""
        from repro.topology import Topology

        space = default_paper_space(num_w=4)
        T, N = 24, 20
        trace, _ = iid_trace(space, TraceSpec(T=T, N=N, seed=3))
        tables = space.tables()
        params = OnAlgoParams(B=jnp.full((N,), 0.08), H=jnp.float32(6e8))
        topo = (None if K is None else Topology.mobility_walk(
            K, N, T, H=6e8, p_handover=0.2, seed=4, streaming=False))
        s1, f1 = simulate_chunked(trace, tables, params, RULE, chunk=8,
                                  block_n=8, topology=topo)
        st, parts = None, []
        for t in range(T):
            tr = dataclasses.replace(
                trace, j_idx=trace.j_idx[t:t + 1],
                d_local=trace.d_local[t:t + 1])
            tp = (None if topo is None else dataclasses.replace(
                topo, assoc=topo.assoc[t:t + 1]))
            s, st = simulate_chunked(tr, tables, params, RULE, chunk=1,
                                     block_n=8, topology=tp, t0=t,
                                     state0=st)
            parts.append(s)
        for k in s1:
            np.testing.assert_array_equal(
                np.concatenate([np.asarray(p[k]) for p in parts]),
                np.asarray(s1[k]), err_msg=k)
        for a, b in ((st.lam, f1.lam), (st.mu, f1.mu),
                     (st.rho.counts, f1.rho.counts), (st.rho.t, f1.rho.t)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_tiled_engine_block_size_independence(self):
        """Every tile width gives the same rollout as the whole-fleet
        chunked kernel."""
        space = default_paper_space(num_w=4)
        trace, _ = iid_trace(space, TraceSpec(T=96, N=24, seed=11))
        tables = space.tables()
        params = OnAlgoParams(B=jnp.full((24,), 0.08), H=jnp.float32(9e8))
        s0, f0 = simulate_chunked(trace, tables, params, RULE, chunk=8)
        for bn in (8, 16, 24):
            s, f = simulate_chunked(trace, tables, params, RULE, chunk=8,
                                    block_n=bn)
            for k in s0:
                np.testing.assert_allclose(
                    np.asarray(s0[k]), np.asarray(s[k]), rtol=2e-5,
                    atol=1e-5, err_msg=f"block_n={bn} series {k}")
            np.testing.assert_allclose(np.asarray(f0.lam),
                                       np.asarray(f.lam), rtol=1e-4,
                                       atol=1e-6)

    def test_chunked_capacity_postpass_matches_scan(self):
        """enforce_slot_capacity on the chunked engine == the scan path:
        admits < offloads under a tight H, and every series agrees."""
        space = default_paper_space(num_w=4)
        trace, _ = iid_trace(space, TraceSpec(T=203, N=16, seed=9))
        tables = space.tables()
        params = OnAlgoParams(B=jnp.full((16,), 0.08), H=jnp.float32(7e8))
        s1, _ = simulate(trace, tables, params, RULE,
                         enforce_slot_capacity=True)
        s2, _ = simulate_chunked(trace, tables, params, RULE, chunk=8,
                                 enforce_slot_capacity=True)
        for k in s1:
            np.testing.assert_allclose(np.asarray(s1[k]), np.asarray(s2[k]),
                                       rtol=2e-5, atol=1e-5, err_msg=k)
        # the capacity rule actually bites under this H
        assert (float(np.sum(np.asarray(s2["admits"])))
                < float(np.sum(np.asarray(s2["offloads"]))))
        # and the default still reports admits == offloads
        s3, _ = simulate_chunked(trace, tables, params, RULE, chunk=8)
        np.testing.assert_array_equal(np.asarray(s3["admits"]),
                                      np.asarray(s3["offloads"]))

    def test_scan_only_options_pin_auto_to_scan(self):
        sc = Scenario("stationary", T=60, N=4, seed=10)
        series, _, _ = run_scenario(sc, engine="auto", with_true_rho=True)
        assert "f_true" in series
        with pytest.raises(ValueError):
            run_scenario(sc, engine="chunked", with_true_rho=True)

    def test_indivisible_chunk_raises(self):
        with pytest.raises(ValueError):
            onalgo_chunked_pallas(
                jnp.zeros((10, 4), jnp.int32), jnp.zeros(4), jnp.float32(0),
                jnp.zeros((4, 8)), jnp.ones(8), jnp.ones(8), jnp.ones(8),
                jnp.ones(4), jnp.float32(1), 0.5, 0.5, chunk=8)


class TestCompose:
    def test_churn_outage_stacks_both_effects(self):
        sc = Scenario("churn_outage", T=500, N=8, seed=3).with_extra(
            churn_frac=0.4, n_outages=2, outage_len=60)
        c = compile_scenario(sc)
        # outage doubled the state space
        assert c.M == 2 * default_paper_space(num_w=sc.num_w).M
        # churn: absent devices sit in the null state
        j = np.asarray(c.trace.j_idx)
        arrive, depart = c.meta["arrive"], c.meta["depart"]
        slots = np.arange(sc.T)[:, None]
        outside = (slots < arrive[None, :]) | (slots >= depart[None, :])
        assert np.all(j[outside] == 0)
        # outage: no offloads while down, some while up
        series, _, _ = run_scenario(c, rule=RULE, engine="scan",
                                    use_kernel=False)
        off = np.asarray(series["offloads"])
        down = c.meta["down"]
        assert off[down].sum() == 0
        assert off[~down].sum() > 0

    def test_compose_explicit_specs(self):
        """compose() layers any modifier kind over any base kind."""
        a = Scenario("bursty", T=300, N=6, seed=4)
        b = Scenario("outage", T=300, N=6, seed=4).with_extra(
            n_outages=1, outage_len=50)
        c = compose(a, b)
        assert c.M == 2 * default_paper_space(num_w=a.num_w).M
        assert "down" in c.meta
        # base kind's traffic survives outside the outage
        j = np.asarray(c.trace.j_idx)
        assert (j > 0).any()

    def test_compose_over_heterogeneous_tables(self):
        """The outage mirror concatenates per-device (N, M) tables too."""
        a = Scenario("heterogeneous", T=200, N=6, seed=5)
        b = Scenario("outage", T=200, N=6, seed=5)
        c = compose(a, b)
        o, h, w = c.tables
        M0 = default_paper_space(num_w=a.num_w).M
        assert o.shape == (6, 2 * M0)
        assert np.all(np.asarray(w[:, M0:]) == 0)

    def test_compose_rejects_mismatched_fleets(self):
        with pytest.raises(ValueError):
            compose(Scenario("stationary", T=100, N=4),
                    Scenario("outage", T=200, N=4))

    def test_compose_rejects_non_modifier(self):
        assert "bursty" not in MODIFIERS
        with pytest.raises(KeyError):
            compose(Scenario("stationary", T=100, N=4),
                    Scenario("bursty", T=100, N=4))

    def test_diurnal_modifier_thins_by_day_cycle(self):
        """diurnal composes as a modifier: traffic peaks at day, thins at
        night, on top of any base kind."""
        T, N = 800, 16
        base = Scenario("bursty", T=T, N=N, seed=1)
        c = compose(base, Scenario("diurnal", T=T, N=N, seed=1).with_extra(
            period=200, amp=0.9))
        base_j = np.asarray(compile_scenario(base).trace.j_idx)
        j = np.asarray(c.trace.j_idx)
        # thinning only: never adds tasks
        assert np.all((j > 0) <= (base_j > 0))
        tasks = (j > 0).mean(axis=1)
        phase = np.sin(2 * np.pi * np.arange(T) / 200)
        assert tasks[phase > 0.7].mean() > tasks[phase < -0.7].mean() + 0.1

    def test_flash_crowd_modifier_densifies_events(self):
        T, N = 400, 8
        base = Scenario("stationary", T=T, N=N, seed=2, task_prob=0.3)
        c = compose(base, Scenario("flash_crowd", T=T, N=N,
                                   seed=2).with_extra(n_events=2,
                                                      event_len=50))
        j = np.asarray(c.trace.j_idx)
        in_event = np.zeros(T, bool)
        for s in c.meta["event_starts"]:
            in_event[s:s + c.meta["event_len"]] = True
        assert (j[in_event] > 0).mean() > (j[~in_event] > 0).mean() + 0.3
        # bootstrap resampling keeps the base state support
        base_j = np.asarray(compile_scenario(base).trace.j_idx)
        for n in range(N):
            assert set(np.unique(j[:, n])) <= set(np.unique(base_j[:, n]))

    def test_modifier_chain_composes_three_deep(self):
        """flash_crowd + outage + churn stack through compose(), and the
        composed trace runs on the chunked engine unchanged."""
        kw = dict(T=320, N=8, seed=5)
        c = compose(compose(compose(Scenario("bursty_counter", **kw),
                                    Scenario("flash_crowd", **kw)),
                            Scenario("outage", **kw).with_extra(
                                n_outages=1, outage_len=60)),
                    Scenario("churn", **kw).with_extra(churn_frac=0.3))
        # outage doubled the space; churn + flash_crowd left tables alone
        assert c.M == 2 * default_paper_space(num_w=c.scenario.num_w).M
        for key in ("event_starts", "down", "arrive"):
            assert key in c.meta
        s1, _, _ = run_scenario(c, rule=RULE, engine="scan",
                                use_kernel=False)
        s2, _, _ = run_scenario(c, rule=RULE, engine="chunked", chunk=8)
        for k in ("reward", "offloads", "tasks", "mu"):
            np.testing.assert_allclose(np.asarray(s1[k]), np.asarray(s2[k]),
                                       rtol=2e-5, atol=1e-5, err_msg=k)
        off = np.asarray(s1["offloads"])
        assert off[c.meta["down"]].sum() == 0


class TestCatalog:
    def test_packaged_catalog_loads_and_compiles(self):
        from repro.scenarios import load_catalog
        cat = load_catalog()
        assert {"paper_bursty", "metro_daily",
                "stadium_flash_outage"} <= set(cat)
        for name, entry in cat.items():
            c = entry.compile()
            assert c.trace.j_idx.shape == (entry.base.T, entry.base.N), name

    def test_compile_named_runs_on_engines(self):
        from repro.scenarios import compile_named
        c = compile_named("stadium_flash_outage")
        s1, _, _ = run_scenario(c, rule=RULE, engine="scan",
                                use_kernel=False)
        off = np.asarray(s1["offloads"])
        down = c.meta["down"]
        assert off[down].sum() == 0 and off[~down].sum() > 0

    def test_modifiers_inherit_base_fleet(self, tmp_path):
        from repro.scenarios.catalog import load_entry
        f = tmp_path / "mini.yaml"
        f.write_text(
            "name: mini\n"
            "base: {kind: stationary, T: 120, N: 4, seed: 1}\n"
            "modifiers:\n"
            "  - {kind: churn, extra: {churn_frac: 0.5}}\n")
        entry = load_entry(f)
        assert entry.modifiers[0].T == 120
        assert entry.modifiers[0].N == 4
        c = entry.compile()
        assert "arrive" in c.meta

    def test_unknown_catalog_name_raises(self):
        from repro.scenarios import compile_named
        with pytest.raises(KeyError, match="catalog"):
            compile_named("no_such_workload")

    def test_bursty_counter_uses_workload_layer(self):
        """The bursty_counter kind's arrivals == the workload layer's
        chain, verbatim (scenario tier and service tier share it)."""
        from repro.workload import arrival_chain_probs, streams
        sc = Scenario("bursty_counter", T=300, N=6, seed=4)
        c = compile_scenario(sc)
        p_on, p_stay, p_init = arrival_chain_probs((5, 10), 8.0)
        u = streams.uniform_block(4, streams.STREAM_SCENARIO, 300, 6, 1)
        u0 = streams.uniform_vector(4, streams.STREAM_ARRIVAL_INIT, 6)
        on = np.asarray(streams.markov_chain(
            u[0], u0 < p_init, jnp.float32(p_on), jnp.float32(p_stay)))
        np.testing.assert_array_equal(np.asarray(c.trace.j_idx) > 0, on)
        assert c.true_rho is not None


class TestSweeps:
    def test_vmapped_sweep_bit_for_bit_vs_loop(self):
        c = compile_scenario(Scenario("stationary", T=300, N=8, seed=11))
        grid = product_grid(8, a_values=(0.2, 0.5), beta_values=(0.0, 0.5),
                            B_values=(0.04, 0.08),
                            H_values=(c.scenario.H,))
        assert grid.G == 8
        sw_series, sw_final = sweep_simulate(c.trace, c.tables, grid)
        for g in range(grid.G):
            p = jax.tree.map(lambda x: x[g], grid.params)
            r = jax.tree.map(lambda x: x[g], grid.rules)
            s, f = simulate(c.trace, c.tables, p, r)
            for k in s:
                np.testing.assert_array_equal(
                    np.asarray(sw_series[k][g]), np.asarray(s[k]),
                    err_msg=f"cell {g} series {k}")
            np.testing.assert_array_equal(np.asarray(sw_final.lam[g]),
                                          np.asarray(f.lam))
            np.testing.assert_array_equal(np.asarray(sw_final.mu[g]),
                                          np.asarray(f.mu))

    def test_grid_from_cells_and_unstack(self):
        params = OnAlgoParams(B=jnp.full((4,), 0.08), H=jnp.float32(5e8))
        grid = grid_from_cells([("r1", StepRule.constant(0.02), params),
                                ("r2", StepRule.inv_sqrt(0.5), params)])
        assert grid.G == 2 and grid.rules.a.shape == (2,)
        c = compile_scenario(Scenario("stationary", T=120, N=4, seed=12))
        series, _ = sweep_simulate(c.trace, c.tables, grid)
        out = dict(unstack_series(series, grid))
        assert set(out) == {"r1", "r2"}
        assert out["r1"]["reward"].shape == (120,)

    def test_mixed_precondition_rejected(self):
        p1 = OnAlgoParams(B=jnp.ones((4,)), H=jnp.float32(1.0))
        p2 = OnAlgoParams(B=jnp.ones((4,)), H=jnp.float32(1.0),
                          precondition=False)
        with pytest.raises(ValueError):
            stack_params([p1, p2])

    def test_chunked_sweep_bit_for_bit_vs_loop(self):
        """sweep_simulate(engine="chunked"): the vmapped batch of fused
        kernel rollouts == a loop of per-cell simulate_chunked calls,
        bit for bit — and tolerance-close to the scan-engine sweep."""
        c = compile_scenario(Scenario("stationary", T=120, N=8, seed=11))
        grid = product_grid(8, a_values=(0.2, 0.5), beta_values=(0.5,),
                            B_values=(0.04, 0.08),
                            H_values=(c.scenario.H,))
        sw_series, sw_final = sweep_simulate(c.trace, c.tables, grid,
                                             engine="chunked", chunk=8,
                                             enforce_slot_capacity=True)
        sc_series, _ = sweep_simulate(c.trace, c.tables, grid,
                                      enforce_slot_capacity=True)
        assert set(sw_series) == set(sc_series)
        for g in range(grid.G):
            p = jax.tree.map(lambda x: x[g], grid.params)
            r = jax.tree.map(lambda x: x[g], grid.rules)
            s, f = simulate_chunked(c.trace, c.tables, p, r, chunk=8,
                                    enforce_slot_capacity=True)
            for k in s:
                np.testing.assert_array_equal(
                    np.asarray(sw_series[k][g]), np.asarray(s[k]),
                    err_msg=f"cell {g} series {k}")
                np.testing.assert_allclose(
                    np.asarray(sw_series[k][g]), np.asarray(sc_series[k][g]),
                    rtol=2e-5, atol=1e-5, err_msg=f"cell {g} vs scan {k}")
            np.testing.assert_array_equal(np.asarray(sw_final.lam[g]),
                                          np.asarray(f.lam))
            np.testing.assert_array_equal(
                np.asarray(sw_final.rho.counts[g]),
                np.asarray(f.rho.counts))

    def test_chunked_sweep_rejects_scan_only_options(self):
        c = compile_scenario(Scenario("stationary", T=60, N=4, seed=1))
        grid = product_grid(4)
        with pytest.raises(ValueError, match="scan-only"):
            sweep_simulate(c.trace, c.tables, grid, engine="chunked",
                           with_true_rho=True)
        with pytest.raises(ValueError, match="engine"):
            sweep_simulate(c.trace, c.tables, grid, engine="warp")

    def test_sweep_with_true_rho_series(self):
        space = default_paper_space(num_w=4)
        trace, rho = iid_trace(space, TraceSpec(T=200, N=4, seed=13))
        grid = product_grid(4, a_values=(0.5,), beta_values=(0.5,),
                            B_values=(0.08,), H_values=(4 * 1e8,))
        series, _ = sweep_simulate(trace, space.tables(), grid,
                                   true_rho=rho, with_true_rho=True)
        assert series["f_true"].shape == (1, 200)
