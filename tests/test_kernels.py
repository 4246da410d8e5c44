"""Per-kernel allclose vs pure-jnp oracles, swept over shapes and dtypes
(interpret mode executes the kernel body on CPU)."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.onalgo_step import (onalgo_chunked_pallas,
                                       onalgo_duals_pallas,
                                       onalgo_tiled_pallas)
from repro.kernels.ssd_chunk import ssd_chunk_pallas


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("B,S,Hq,Hkv,D", [
        (1, 128, 4, 4, 64),     # MHA
        (2, 256, 8, 2, 64),     # GQA 4:1
        (1, 512, 4, 1, 128),    # MQA, 128 head dim
        (2, 128, 2, 2, 32),
    ])
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, B, S, Hq, Hkv, D, causal, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, S, Hq, D), dtype)
        k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
        v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
        out = flash_attention_pallas(q, k, v, causal=causal, block_q=64,
                                     block_k=64)
        want = ref.flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            **_tol(dtype))

    def test_block_shape_independence(self):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 256, 4, 64))
        k = jax.random.normal(ks[1], (1, 256, 2, 64))
        v = jax.random.normal(ks[2], (1, 256, 2, 64))
        outs = [np.asarray(flash_attention_pallas(
            q, k, v, causal=True, block_q=bq, block_k=bk))
            for bq, bk in [(64, 64), (128, 64), (64, 128), (256, 256)]]
        for o in outs[1:]:
            np.testing.assert_allclose(o, outs[0], rtol=1e-5, atol=1e-5)


class TestDecodeAttention:
    @pytest.mark.parametrize("B,S,Hq,Hkv,D", [
        (2, 256, 8, 2, 64),
        (1, 512, 4, 4, 128),
        (4, 128, 2, 1, 32),
    ])
    @pytest.mark.parametrize("frac", [0.25, 0.8, 1.0])
    def test_matches_oracle(self, B, S, Hq, Hkv, D, frac):
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (B, 1, Hq, D))
        kc = jax.random.normal(ks[1], (B, S, Hkv, D))
        vc = jax.random.normal(ks[2], (B, S, Hkv, D))
        n = max(1, int(S * frac))
        out = decode_attention_pallas(q, kc, vc, n, block_k=64)
        want = ref.decode_attention_ref(q, kc, vc, n)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_bf16(self):
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (2, 1, 4, 64), jnp.bfloat16)
        kc = jax.random.normal(ks[1], (2, 128, 2, 64), jnp.bfloat16)
        vc = jax.random.normal(ks[2], (2, 128, 2, 64), jnp.bfloat16)
        out = decode_attention_pallas(q, kc, vc, 100)
        want = ref.decode_attention_ref(q, kc, vc, 100)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)


class TestSSDChunk:
    @pytest.mark.parametrize("b,nc,Q,h,p,n", [
        (1, 2, 128, 2, 64, 32),
        (2, 1, 64, 4, 32, 128),
        (1, 4, 128, 8, 64, 16),
    ])
    def test_matches_oracle(self, b, nc, Q, h, p, n):
        ks = jax.random.split(jax.random.PRNGKey(4), 5)
        x = jax.random.normal(ks[0], (b, nc, Q, h, p))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, nc, Q, h))) * 0.5
        A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
        Bh = jax.random.normal(ks[3], (b, nc, Q, h, n)) * 0.5
        Ch = jax.random.normal(ks[4], (b, nc, Q, h, n)) * 0.5
        y, st = ssd_chunk_pallas(x, dt, A, Bh, Ch)
        y2, st2 = ref.ssd_chunk_ref(x, dt, A, Bh, Ch)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y2),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(st), np.asarray(st2),
                                   rtol=1e-4, atol=1e-4)

    def test_end_to_end_mamba_block_kernel_path(self):
        from repro.configs import get_config
        from repro.models import lm
        cfg = get_config("mamba2_370m").reduced()
        params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                                  cfg.vocab_size)
        l_ref, _ = lm.lm_loss(cfg, params, {"tokens": toks},
                              use_kernel=False)
        l_ker, _ = lm.lm_loss(cfg, params, {"tokens": toks}, use_kernel=True)
        assert abs(float(l_ref) - float(l_ker)) < 1e-4


class TestOnAlgoKernel:
    @pytest.mark.parametrize("N,M", [(4, 7), (100, 37), (256, 37), (1000, 97)])
    def test_matches_oracle(self, N, M):
        ks = jax.random.split(jax.random.PRNGKey(5), 6)
        lam = jax.random.uniform(ks[0], (N,))
        mu = jnp.float32(0.3)
        rho = jax.random.dirichlet(ks[1], jnp.ones(M), (N,))
        o = jax.random.uniform(ks[2], (M,))
        h = jax.random.uniform(ks[3], (M,))
        w = jax.random.uniform(ks[4], (M,)) - 0.2
        B = jax.random.uniform(ks[5], (N,)) + 0.05
        g1, l1 = onalgo_duals_pallas(lam, mu, rho, o, h, w, B)
        g2, l2 = ref.onalgo_duals_ref(lam, mu, rho, o, h, w, B)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)

    @pytest.mark.parametrize("N,M,T,chunk,block_n", [
        (20, 16, 64, 8, 8),     # N not divisible by the tile (3 tiles)
        (24, 37, 96, 16, 8),    # M needs lane padding
        (50, 23, 40, 8, 16),    # 4 tiles, padded tail tile
        (8, 16, 64, 8, 8),      # single-tile edge (phase 2 == phase 1 step)
    ])
    def test_tiled_matches_chunked_oracle(self, N, M, T, chunk, block_n):
        """Device-tiled kernel == sequential oracle: same decisions, duals,
        mu/lam_norm series, and visit counts."""
        ks = jax.random.split(jax.random.PRNGKey(N + M), 6)
        j = jax.random.randint(ks[0], (T, N), 0, M)
        o = jax.random.uniform(ks[1], (M,))
        h = jax.random.uniform(ks[2], (M,))
        w = jax.random.uniform(ks[3], (M,)) - 0.2
        B = jax.random.uniform(ks[4], (N,)) + 0.05
        lam0 = jax.random.uniform(ks[5], (N,)) * 0.1
        args = (j, lam0, jnp.float32(0.05), jnp.zeros((N, M)), o, h, w, B,
                jnp.float32(2.0), 0.4, 0.5)
        off_k, mu_k, ln_k, lam_k, mufin_k, cnt_k = onalgo_tiled_pallas(
            *args, chunk=chunk, block_n=block_n, interpret=True)
        off_r, mu_r, ln_r, lam_r, mufin_r, cnt_r = \
            ref.onalgo_chunked_ref(*args)
        np.testing.assert_array_equal(np.asarray(off_k), np.asarray(off_r))
        np.testing.assert_allclose(np.asarray(mu_k), np.asarray(mu_r),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(lam_k), np.asarray(lam_r),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(ln_k), np.asarray(ln_r),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(cnt_k), np.asarray(cnt_r))
        assert float(mufin_k) == pytest.approx(float(mufin_r), rel=1e-5)

    @pytest.mark.parametrize("block_n", [None, 8])
    def test_slot_values_overlay_matches_oracle(self, block_n):
        """The service-overlay slot-value streams drive the realized
        decision identically in the chunked/tiled kernels and the
        sequential oracle (raw values for decisions, tables for duals,
        null slots gated)."""
        N, M, T, chunk = 20, 16, 64, 8
        ks = jax.random.split(jax.random.PRNGKey(11), 9)
        j = jax.random.randint(ks[0], (T, N), 0, M)
        o = jax.random.uniform(ks[1], (M,))
        h = jax.random.uniform(ks[2], (M,))
        w = jax.random.uniform(ks[3], (M,)) - 0.2
        B = jax.random.uniform(ks[4], (N,)) + 0.05
        lam0 = jax.random.uniform(ks[5], (N,)) * 0.1
        sv = (jax.random.uniform(ks[6], (T, N)),
              jax.random.uniform(ks[7], (T, N)),
              jax.random.uniform(ks[8], (T, N)) - 0.1)
        args = (j, lam0, jnp.float32(0.05), jnp.zeros((N, M)), o, h, w, B,
                jnp.float32(2.0), 0.4, 0.5)
        kern = (onalgo_chunked_pallas if block_n is None
                else partial(onalgo_tiled_pallas, block_n=block_n))
        out_k = kern(*args, chunk=chunk, slot_values=sv, interpret=True)
        out_r = ref.onalgo_chunked_ref(*args, slot_values=sv)
        np.testing.assert_array_equal(np.asarray(out_k[0]),
                                      np.asarray(out_r[0]))
        for i in (1, 2, 3):
            np.testing.assert_allclose(np.asarray(out_k[i]),
                                       np.asarray(out_r[i]), rtol=1e-5,
                                       atol=1e-6)
        # null slots never offload, whatever the raw gain says
        assert not np.asarray(out_k[0])[np.asarray(j) == 0].any()

    @pytest.mark.parametrize("n_tiles", [2, 5])
    def test_tiled_state_carry_across_tiles_and_calls(self, n_tiles):
        """The tiled kernel keeps lam, the visit counts and the chunk's
        decision band in HBM and streams each tile through VMEM with
        explicit async copies (no output block is ever read back).  Over
        n_tiles > 1, several chunks, nonzero seeds and a resumed second
        call at a traced t0, it matches one sequential oracle call."""
        N, M, T, chunk, block_n, t0 = 8 * n_tiles - 3, 11, 48, 8, 8, 16
        ks = jax.random.split(jax.random.PRNGKey(n_tiles), 7)
        j = jax.random.randint(ks[0], (T, N), 0, M)
        o = jax.random.uniform(ks[1], (N, M))
        h = jax.random.uniform(ks[2], (M,))
        w = jax.random.uniform(ks[3], (M,)) - 0.2
        B = jax.random.uniform(ks[4], (N,)) + 0.05
        lam0 = jax.random.uniform(ks[5], (N,)) * 0.1
        counts0 = jax.random.randint(ks[6], (N, M), 0, 3).astype(
            jnp.float32)
        tabs = (o, h, w, B, jnp.float32(2.0), 0.4, 0.5)
        want = ref.onalgo_chunked_ref(j, lam0, jnp.float32(0.05), counts0,
                                      *tabs, t0=t0)
        half = T // 2
        first = onalgo_tiled_pallas(j[:half], lam0, jnp.float32(0.05),
                                    counts0, *tabs, chunk=chunk,
                                    block_n=block_n, t0=jnp.int32(t0))
        second = onalgo_tiled_pallas(j[half:], first[3], first[4],
                                     first[5], *tabs, chunk=chunk,
                                     block_n=block_n,
                                     t0=jnp.int32(t0 + half))
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(first[0]), np.asarray(second[0])]),
            np.asarray(want[0]))
        for i in (1, 2):  # mu and lam-norm series
            np.testing.assert_allclose(
                np.concatenate([np.asarray(first[i]),
                                np.asarray(second[i])]),
                np.asarray(want[i]), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(second[3]),
                                   np.asarray(want[3]), rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(second[5]),
                                      np.asarray(want[5]))

    def test_tiled_per_device_tables(self):
        """(N, M) heterogeneous tables stream tile by tile too."""
        N, M, T = 20, 37, 48
        ks = jax.random.split(jax.random.PRNGKey(1), 5)
        j = jax.random.randint(ks[0], (T, N), 0, M)
        o = jax.random.uniform(ks[1], (N, M))
        h = jax.random.uniform(ks[2], (N, M))
        w = jax.random.uniform(ks[3], (N, M)) - 0.2
        B = jax.random.uniform(ks[4], (N,)) + 0.05
        args = (j, jnp.zeros((N,)), jnp.float32(0.0), jnp.zeros((N, M)),
                o, h, w, B, jnp.float32(3.0), 0.5, 0.5)
        out_k = onalgo_tiled_pallas(*args, chunk=8, block_n=8,
                                    interpret=True)
        out_r = ref.onalgo_chunked_ref(*args)
        np.testing.assert_array_equal(np.asarray(out_k[0]),
                                      np.asarray(out_r[0]))
        np.testing.assert_allclose(np.asarray(out_k[3]),
                                   np.asarray(out_r[3]), rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_array_equal(np.asarray(out_k[5]),
                                      np.asarray(out_r[5]))

    def test_tiled_rejects_bad_block(self):
        args = (jnp.zeros((16, 4), jnp.int32), jnp.zeros(4),
                jnp.float32(0), jnp.zeros((4, 8)), jnp.ones(8),
                jnp.ones(8), jnp.ones(8), jnp.ones(4), jnp.float32(1),
                0.5, 0.5)
        with pytest.raises(ValueError):
            onalgo_tiled_pallas(*args, chunk=8, block_n=6)  # not 8-mult
        with pytest.raises(ValueError):
            onalgo_tiled_pallas(*args, chunk=5, block_n=8)  # T % chunk

    def test_simulation_path_with_kernel(self):
        """fleet.simulate(use_kernel=True) == jnp path, slot for slot."""
        import numpy as np
        from repro.core import (OnAlgoParams, StepRule, default_paper_space,
                                simulate)
        from repro.data.traces import TraceSpec, iid_trace
        space = default_paper_space(num_w=4)
        trace, _ = iid_trace(space, TraceSpec(T=300, N=16, seed=7))
        tables = space.tables()
        params = OnAlgoParams(B=jnp.full((16,), 0.08), H=jnp.float32(7e8))
        rule = StepRule.inv_sqrt(0.5)
        s1, f1 = simulate(trace, tables, params, rule, use_kernel=False)
        s2, f2 = simulate(trace, tables, params, rule, use_kernel=True)
        np.testing.assert_allclose(np.asarray(s1["reward"]),
                                   np.asarray(s2["reward"]), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(f1.lam), np.asarray(f2.lam),
                                   rtol=1e-4, atol=1e-6)
