"""Workload layer: counter-based streams, the versioned RNG contract,
the service workload processes, and the streaming (chunk-addressable)
lowering."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.workload import (RNG_COUNTER, RNG_LEGACY_HOST,
                            arrival_chain_probs, generate_service_workload,
                            lower_service_workload, streams,
                            validate_rng_version)


class TestStreams:
    def test_draws_are_addressed_not_ordered(self):
        """Same (seed, sid) => identical grid, independent of call order;
        different sids / seeds decorrelate."""
        a1 = np.asarray(streams.uniforms(0, 1, 100, 8))
        _ = streams.uniforms(3, 2, 50, 4)  # unrelated draw in between
        a2 = np.asarray(streams.uniforms(0, 1, 100, 8))
        np.testing.assert_array_equal(a1, a2)
        b = np.asarray(streams.uniforms(0, 2, 100, 8))
        c = np.asarray(streams.uniforms(1, 1, 100, 8))
        assert np.abs(a1 - b).max() > 1e-3
        assert np.abs(a1 - c).max() > 1e-3

    def test_horizon_extension_preserves_prefix(self):
        """Extending T must not perturb already-generated slots (block
        keys and in-block counters are horizon-independent), including
        non-multiples of the ROW_BLOCK contract constant."""
        short = np.asarray(streams.uniform_block(5, 1, 200, 6, 4))
        for T in (201, 256, 1000):
            long = np.asarray(streams.uniform_block(5, 1, T, 6, 4))
            np.testing.assert_array_equal(long[:, :200], short)

    def test_uniform_block_channels_decorrelated(self):
        u = np.asarray(streams.uniform_block(0, 1, 500, 4, 3))
        assert u.shape == (3, 500, 4)
        for c in range(1, 3):
            r = np.corrcoef(u[0].ravel(), u[c].ravel())[0, 1]
            assert abs(r) < 0.1

    def test_counter_draw_is_the_flag_off_uniform(self):
        """The explicit-counter draw is the stream contract: it equals
        ``jax.random.uniform`` made with ``jax_threefry_partitionable``
        off — the layout the streams were defined under — whatever the
        flag's default, for block grids and per-device vectors alike."""
        key = streams._block_keys(3, 1, 1, 5)[0]
        with jax.threefry_partitionable(False):
            want = np.asarray(jax.random.uniform(key, (streams.ROW_BLOCK,
                                                       2, 5)))
            want_vec = np.asarray(jax.random.uniform(
                streams.stream_key(0, 2), (7,)))
        got = np.asarray(streams.uniform_block_range(3, 1, 5, 1, 5, 2))
        np.testing.assert_array_equal(got.transpose(1, 0, 2), want)
        for flag in (True, False):  # the draw ignores the flag
            with jax.threefry_partitionable(flag):
                np.testing.assert_array_equal(
                    np.asarray(streams.uniform_vector(0, 2, 7)), want_vec)

    def test_column_range_bit_identical_to_full_width(self):
        """The counter-offset column draw (shard-local generation) must
        reproduce EXACTLY the corresponding columns of the full-width
        draw — both go through the one explicit-counter function."""
        full = np.asarray(streams.uniform_block_range(3, 1, 2, 3, 11, 4))
        for n0, nc in ((0, 11), (0, 3), (4, 5), (10, 1)):
            cols = np.asarray(streams.uniform_block_range(
                3, 1, 2, 3, 11, 4, n0=n0, n_cols=nc))
            np.testing.assert_array_equal(cols, full[:, :, n0:n0 + nc],
                                          err_msg=str((n0, nc)))

    def test_column_range_traced_offset(self):
        """n0 may be traced (an axis_index inside shard_map); the columns
        equal the explicit-counter draw of the full-width grid."""
        half = streams.ROW_BLOCK // 2
        r, c, n = np.meshgrid(np.arange(half), np.arange(2), np.arange(9),
                              indexing="ij")
        x0 = jnp.asarray((r * 2 + c) * 9 + n, jnp.uint32)
        full = np.concatenate(
            [np.concatenate(streams._uniform_pairs(k, x0, x0 + half * 18))
             for k in streams._block_keys(7, 2, 2)]).transpose(1, 0, 2)
        f = jax.jit(lambda n0: streams.uniform_block_range(
            7, 2, 0, 2, 9, 2, n0=n0, n_cols=3))
        np.testing.assert_array_equal(np.asarray(f(jnp.int32(4))),
                                      full[:, :, 4:7])

    def test_levels_from_uniform_covers_range(self):
        u = streams.uniforms(0, 1, 400, 8)
        lv = np.asarray(streams.levels_from_uniform(u, 5))
        assert lv.min() == 0 and lv.max() == 4
        # roughly uniform occupancy
        counts = np.bincount(lv.ravel(), minlength=5) / lv.size
        assert np.all(np.abs(counts - 0.2) < 0.05)

    def test_markov_chain_matches_transition_probs(self):
        T, N = 4000, 16
        u = streams.uniforms(0, 1, T, N)
        on = np.asarray(streams.markov_chain(
            u, jnp.zeros((N,), bool), jnp.float32(0.2), jnp.float32(0.7)))
        prev, cur = on[:-1].ravel(), on[1:].ravel()
        p_on = cur[~prev].mean()
        p_stay = cur[prev].mean()
        assert p_on == pytest.approx(0.2, abs=0.02)
        assert p_stay == pytest.approx(0.7, abs=0.02)

    def test_markov_chain_equals_sequential_reference(self):
        """The associative-scan chain == a plain per-slot host rollout."""
        T, N = 257, 5
        u = np.asarray(streams.uniforms(9, 1, T, N))
        s0 = np.asarray(
            jax.random.uniform(streams.stream_key(9, 2), (N,))) < 0.5
        on = np.asarray(streams.markov_chain(
            jnp.asarray(u), jnp.asarray(s0), jnp.float32(0.15),
            jnp.float32(0.85)))
        ref = np.zeros((T, N), bool)
        s = s0.copy()
        for t in range(T):
            s = np.where(s, u[t] < 0.85, u[t] < 0.15)
            ref[t] = s
        np.testing.assert_array_equal(on, ref)

    def test_hold_resample_holds_between_changes(self):
        T, N = 300, 4
        u = streams.uniform_block(3, 1, T, N, 2)
        cand = streams.levels_from_uniform(u[1], 7)
        out = np.asarray(streams.hold_resample(u[0] < 0.1, cand))
        change = np.array(u[0] < 0.1)
        change[0] = True
        cand = np.asarray(cand)
        # at change slots the value is that slot's candidate...
        np.testing.assert_array_equal(out[change], cand[change])
        # ...elsewhere it equals the previous slot's value
        hold = ~change[1:]
        np.testing.assert_array_equal(out[1:][hold], out[:-1][hold])


class TestServiceWorkload:
    def test_generation_is_jitted_and_deterministic(self):
        wl1 = generate_service_workload(4, 300, 6, 64, 3)
        wl2 = generate_service_workload(4, 300, 6, 64, 3)
        for f in ("on", "img", "rates"):
            np.testing.assert_array_equal(np.asarray(getattr(wl1, f)),
                                          np.asarray(getattr(wl2, f)))
        assert np.asarray(wl1.img).max() < 64
        assert np.asarray(wl1.rates).max() < 3

    def test_arrival_stats_match_chain_targets(self):
        p_on, p_stay, p_init = arrival_chain_probs((5, 10), 8.0)
        wl = generate_service_workload(0, 6000, 16, 64, 3)
        on = np.asarray(wl.on)
        assert on.mean() == pytest.approx(p_init, abs=0.03)
        prev, cur = on[:-1].ravel(), on[1:].ravel()
        assert cur[prev].mean() == pytest.approx(p_stay, abs=0.02)
        assert cur[~prev].mean() == pytest.approx(p_on, abs=0.02)

    def test_channel_stay_probability(self):
        wl = generate_service_workload(2, 6000, 8, 64, 3)
        r = np.asarray(wl.rates)
        same = (r[1:] == r[:-1]).mean()
        # stay w.p. 0.9 plus 1/3 chance a redraw repeats the level
        assert same == pytest.approx(0.9 + 0.1 / 3, abs=0.02)

    def test_rng_contract_validation(self):
        assert validate_rng_version(RNG_COUNTER) == 1
        # v0 is retired: only the pinned golden fixture still speaks it
        with pytest.raises(ValueError, match="retired"):
            validate_rng_version(RNG_LEGACY_HOST)
        with pytest.raises(ValueError, match="rng_version"):
            validate_rng_version(2)

    def test_legacy_v0_draw_order_is_stable(self):
        """The frozen v0 sampler (test-support, tests/legacy_workload.py)
        replays the retired legacy loop's draw order — pinned here so
        the golden fixture's inputs can't silently move."""
        from legacy_workload import bursty_arrivals, legacy_service_workload
        on, img, rates = legacy_service_workload(0, 50, 3, 16, 3, (5, 10),
                                                 8.0)
        rng = np.random.default_rng(0)
        on_ref = bursty_arrivals(rng, 50, 3, (5, 10), 8.0)
        rate_idx = rng.integers(0, 3, 3)
        np.testing.assert_array_equal(on, on_ref)
        img_ref = np.zeros((50, 3), np.int64)
        rates_ref = np.zeros((50, 3), np.int64)
        for t in range(50):
            img_ref[t] = rng.integers(0, 16, 3)
            flip = rng.random(3) > 0.9
            rate_idx = np.where(flip, rng.integers(0, 3, 3), rate_idx)
            rates_ref[t] = rate_idx
        np.testing.assert_array_equal(img, img_ref)
        np.testing.assert_array_equal(rates, rates_ref)


class TestStreamingWorkload:
    """The chunk-addressable lowering: slabs must be bit-identical to
    the one-shot materialization — slab boundaries are unobservable."""

    T, N = 331, 6

    @pytest.fixture(scope="class")
    def pair(self):
        ref = generate_service_workload(4, self.T, self.N, 64, 3,
                                        mean_gap=6.0)
        wl = lower_service_workload(4, self.T, self.N, 64, 3,
                                    mean_gap=6.0)
        return ref, wl

    def _assert_slab(self, ref, slab, t0):
        for f in ("on", "img", "rates"):
            np.testing.assert_array_equal(
                np.asarray(getattr(slab, f)),
                np.asarray(getattr(ref, f))[t0:t0 + slab.on.shape[0]],
                err_msg=f"field {f} at t0={t0}")

    def test_full_horizon_single_slab(self, pair):
        ref, wl = pair
        self._assert_slab(ref, wl.slab(0, self.T), 0)

    @pytest.mark.parametrize("t0", [0, 1, 37, 63, 64, 65, 200, 331 - 41])
    def test_arbitrary_offsets(self, pair, t0):
        """Offsets crossing, touching, and straddling ROW_BLOCK
        boundaries, all against the same materialized realization."""
        ref, wl = pair
        self._assert_slab(ref, wl.slab(t0, 41), t0)

    def test_covering_chunk_walk_non_divisible(self, pair):
        """A chunked walk with T % slab != 0 reassembles the horizon."""
        ref, wl = pair
        for t0 in range(0, self.T, 48):
            L = min(48, self.T - t0)
            self._assert_slab(ref, wl.slab(t0, L), t0)

    def test_slab_jits_with_traced_offset(self, pair):
        """One compiled slab function serves every offset (the engines
        sweep t0 as a traced scalar)."""
        ref, wl = pair
        slab = jax.jit(lambda wl, t0: wl.slab(t0, 40))
        for t0 in (0, 65, 130):
            self._assert_slab(ref, slab(wl, jnp.int32(t0)), t0)

    def test_lowering_is_T_extension_stable(self):
        """Extending the lowering horizon preserves boundary states —
        the streaming analogue of prefix stability."""
        short = lower_service_workload(7, 200, 5, 64, 3)
        long = lower_service_workload(7, 500, 5, 64, 3)
        nb = short.n_blocks
        np.testing.assert_array_equal(np.asarray(short.on_entry),
                                      np.asarray(long.on_entry)[:nb])
        np.testing.assert_array_equal(np.asarray(short.rate_entry),
                                      np.asarray(long.rate_entry)[:nb])
