"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(+ hypothesis property sweeps)."""

import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dev dep; skip, don't abort collection
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.ops import crossentropy_op, flash_attention_op, ssd_op


RNG = np.random.RandomState(0)


def randn(*shape, dtype=np.float32, scale=1.0):
    return jnp.asarray((RNG.randn(*shape) * scale).astype(dtype))


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,Hq,Hkv,S,D,bq,bk",
        [
            (1, 2, 2, 64, 32, 16, 16),    # MHA
            (2, 4, 2, 128, 32, 32, 32),   # GQA 2x
            (1, 8, 1, 96, 16, 32, 32),    # MQA, non-multiple seq (pad path)
            (1, 2, 2, 128, 128, 128, 64), # MXU-width head_dim
        ],
    )
    def test_matches_ref(self, dtype, B, Hq, Hkv, S, D, bq, bk):
        q = randn(B, Hq, S, D).astype(dtype)
        k = randn(B, Hkv, S, D).astype(dtype)
        v = randn(B, Hkv, S, D).astype(dtype)
        out = flash_attention_op(q, k, v, causal=True, block_q=bq, block_k=bk)
        expect = ref.attention_ref(q, k, v, causal=True)
        tol = 1e-4 if dtype == np.float32 else 2e-2
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(expect, np.float32), atol=tol, rtol=tol
        )

    @pytest.mark.parametrize("window", [8, 32, 100])
    def test_sliding_window(self, window):
        q, k, v = (randn(1, 2, 64, 16) for _ in range(3))
        out = flash_attention_op(q, k, v, causal=True, window=window, block_q=16, block_k=16)
        expect = ref.attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-4)

    @pytest.mark.parametrize("softcap", [10.0, 50.0])
    def test_softcap(self, softcap):
        q, k, v = (randn(1, 2, 64, 16, scale=3.0) for _ in range(3))
        out = flash_attention_op(q, k, v, causal=True, softcap=softcap, block_q=32, block_k=32)
        expect = ref.attention_ref(q, k, v, causal=True, softcap=softcap)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-4)

    def test_non_causal(self):
        q, k, v = (randn(1, 2, 48, 16) for _ in range(3))
        out = flash_attention_op(q, k, v, causal=False, block_q=16, block_k=16)
        expect = ref.attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-4)

    @settings(deadline=None, max_examples=10)
    @given(
        S=st.integers(16, 80),
        D=st.sampled_from([8, 16, 32]),
        Hkv=st.sampled_from([1, 2]),
        g=st.sampled_from([1, 2, 4]),
    )
    def test_property_sweep(self, S, D, Hkv, g):
        rng = np.random.RandomState(S * 7 + D)
        q = jnp.asarray(rng.randn(1, Hkv * g, S, D).astype(np.float32))
        k = jnp.asarray(rng.randn(1, Hkv, S, D).astype(np.float32))
        v = jnp.asarray(rng.randn(1, Hkv, S, D).astype(np.float32))
        out = flash_attention_op(q, k, v, causal=True, block_q=16, block_k=16)
        expect = ref.attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-4)


class TestSSD:
    @pytest.mark.parametrize("S,P,N,chunk", [(64, 16, 8, 16), (128, 32, 16, 32), (32, 8, 8, 32)])
    def test_matches_sequential_ref(self, S, P, N, chunk):
        BH = 3
        x = randn(BH, S, P)
        dt = jnp.abs(randn(BH, S)) * 0.5
        A = -jnp.abs(randn(BH))
        Bm = randn(BH, S, N)
        Cm = randn(BH, S, N)
        y, fin = ssd_op(x, dt, A, Bm, Cm, chunk=chunk)
        for i in range(BH):
            yr, fr = ref.ssd_ref(
                x[i : i + 1, :, None], dt[i : i + 1, :, None], A[i : i + 1],
                Bm[i : i + 1, :, None], Cm[i : i + 1, :, None],
            )
            np.testing.assert_allclose(np.asarray(y[i]), np.asarray(yr[0, :, 0]), atol=2e-3)
            np.testing.assert_allclose(np.asarray(fin[i]), np.asarray(fr[0, 0]), atol=2e-3)

    def test_chunked_jnp_matches_kernel_path(self):
        """models.mamba2.ssd_chunked (the lowered path) == Pallas kernel."""
        from repro.models.mamba2 import ssd_chunked

        B, S, H, P, N = 2, 64, 4, 16, 8
        x = randn(B, S, H, P)
        dt = jnp.abs(randn(B, S, H)) * 0.5
        A = -jnp.abs(randn(H))
        Bm = randn(B, S, 1, N)
        Cm = randn(B, S, 1, N)
        y_jnp, fin_jnp = ssd_chunked(x, dt, A, Bm, Cm, chunk=16)
        # fold to kernel layout [B*H, S, ...]
        xk = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
        dtk = dt.transpose(0, 2, 1).reshape(B * H, S)
        Ak = jnp.tile(A, B)
        Bk = jnp.repeat(Bm.transpose(0, 2, 1, 3), H, axis=1).reshape(B * H, S, N)
        Ck = jnp.repeat(Cm.transpose(0, 2, 1, 3), H, axis=1).reshape(B * H, S, N)
        y_k, fin_k = ssd_op(xk, dtk, Ak, Bk, Ck, chunk=16)
        np.testing.assert_allclose(
            np.asarray(y_k.reshape(B, H, S, P).transpose(0, 2, 1, 3)),
            np.asarray(y_jnp), atol=2e-3,
        )
        np.testing.assert_allclose(
            np.asarray(fin_k.reshape(B, H, P, N)), np.asarray(fin_jnp), atol=2e-3
        )


class TestCrossEntropy:
    @pytest.mark.parametrize("T,D,V,bt,bv", [(64, 32, 500, 32, 128), (100, 48, 1000, 32, 256), (16, 16, 50, 16, 64)])
    def test_matches_ref(self, T, D, V, bt, bv):
        x = randn(T, D)
        w = randn(D, V, scale=0.05)
        labels = jnp.asarray(RNG.randint(0, V, (T,)).astype(np.int32))
        nll = crossentropy_op(x, w, labels, block_t=bt, block_v=bv)
        expect = ref.crossentropy_ref(x, w, labels)
        np.testing.assert_allclose(np.asarray(nll), np.asarray(expect), atol=1e-4, rtol=1e-4)

    def test_softcap_and_bf16(self):
        x = randn(32, 16).astype(jnp.bfloat16)
        w = randn(16, 100, scale=0.2).astype(jnp.bfloat16)
        labels = jnp.asarray(RNG.randint(0, 100, (32,)).astype(np.int32))
        nll = crossentropy_op(x, w, labels, softcap=30.0, block_t=16, block_v=64)
        expect = ref.crossentropy_ref(x, w, labels, softcap=30.0)
        np.testing.assert_allclose(np.asarray(nll), np.asarray(expect), atol=5e-2, rtol=5e-2)

    def test_matches_model_chunked_ce(self):
        """kernels CE == models.layers.cross_entropy_chunked (train path)."""
        from repro.models.layers import cross_entropy_chunked

        B, S, D, V = 2, 32, 16, 128
        x = randn(B, S, D)
        w = randn(D, V, scale=0.1)
        labels = jnp.asarray(RNG.randint(0, V, (B, S)).astype(np.int32))
        mean_chunked = cross_entropy_chunked(x, w, labels, chunk=8)
        nll = crossentropy_op(x.reshape(B * S, D), w, labels.reshape(-1), block_t=16, block_v=64)
        np.testing.assert_allclose(float(mean_chunked), float(nll.mean()), atol=1e-4)


class TestSLSTMKernel:
    @pytest.mark.parametrize("B,S,H,D,bt", [(4, 24, 2, 8, 2), (2, 16, 4, 16, 2), (8, 8, 2, 8, 8)])
    def test_matches_model_scan(self, B, S, H, D, bt):
        from repro.kernels.slstm import slstm_scan
        from repro.models.ssm_xlstm import _slstm_scan, empty_slstm_state

        rng = np.random.RandomState(B * 31 + S)
        d = H * D

        class Cfg:
            n_heads = H
            d_model = d
            norm_eps = 1e-6

        u = rng.randn(B, S, 4 * d).astype(np.float32) * 0.5
        R = rng.randn(4, H, D, D).astype(np.float32) * 0.2
        p = {"r_zifo": jnp.asarray(R)}
        hs_ref, fin_ref = _slstm_scan(p, jnp.asarray(u), Cfg, empty_slstm_state(Cfg, B))
        uk = jnp.asarray(u).reshape(B, S, 4, H, D).transpose(1, 0, 2, 3, 4)
        h_seq, (c, n, h, m) = slstm_scan(uk, jnp.asarray(R), batch_tile=bt, interpret=True)
        hs_k = h_seq.transpose(1, 0, 2, 3).reshape(B, S, d)
        np.testing.assert_allclose(np.asarray(hs_k), np.asarray(hs_ref), atol=1e-4)
        np.testing.assert_allclose(np.asarray(c), np.asarray(fin_ref["c"]), atol=1e-4)
        np.testing.assert_allclose(np.asarray(m), np.asarray(fin_ref["m"]), atol=1e-4)


class TestMLSTMParallelVsRecurrent:
    def test_chunked_parallel_matches_recurrence(self):
        from repro.models.ssm_xlstm import mlstm_parallel

        B, S, H, D = 1, 32, 2, 8
        q = randn(B, S, H, D)
        k = randn(B, S, H, D) / np.sqrt(D)
        v = randn(B, S, H, D)
        logi = randn(B, S, H, scale=0.5)
        logf = jnp.asarray(np.log(RNG.uniform(0.8, 0.999, (B, S, H))).astype(np.float32))
        h_par = mlstm_parallel(q, k, v, logi, logf, q_chunk=8)
        h_rec = ref.mlstm_ref(q, k, v, logi, logf)
        np.testing.assert_allclose(np.asarray(h_par), np.asarray(h_rec), atol=2e-3)


class TestParzenScoreKernel:
    def _mixture(self, rng, k):
        mus = rng.uniform(-3, 3, k).astype(np.float32)
        sigmas = rng.uniform(0.05, 1.0, k).astype(np.float32)
        ln = (np.log(np.full(k, 1.0 / k)) - np.log(sigmas)).astype(np.float32)
        return jnp.asarray(mus), jnp.asarray(sigmas), jnp.asarray(ln)

    @pytest.mark.parametrize(
        "C,Kl,Kg,bc,bk",
        [
            (64, 8, 8, 32, 8),      # single component block
            (100, 16, 64, 32, 16),  # unequal sides + non-multiple candidates
            (256, 32, 32, 64, 8),   # multi-block reduction axis
            (512, 128, 256, 256, 64),
        ],
    )
    def test_matches_ref(self, C, Kl, Kg, bc, bk):
        from repro.kernels.parzen import parzen_score

        rng = np.random.RandomState(C + Kl + Kg)
        cands = jnp.asarray(rng.uniform(-4, 4, C).astype(np.float32))
        l = self._mixture(rng, Kl)
        g = self._mixture(rng, Kg)
        out = parzen_score(cands, *l, *g, block_c=bc, block_k=bk, interpret=True)
        expect = ref.parzen_score_ref(cands, *l, *g)
        assert out.shape == (C,)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-4, rtol=1e-4)

    def test_neg_inf_padding_components_are_inert(self):
        """pow2 padding carries log_norm = -inf: scores must equal the
        unpadded mixture's exactly (the kernel clamps, never NaNs)."""
        from repro.kernels.parzen import parzen_score

        rng = np.random.RandomState(0)
        cands = jnp.asarray(rng.uniform(-4, 4, 64).astype(np.float32))
        mus, sigmas, ln = self._mixture(rng, 5)
        pad = lambda v, fill: jnp.pad(v, (0, 3), constant_values=fill)
        padded = (pad(mus, 0.0), pad(sigmas, 1.0), pad(ln, -np.inf))
        out = parzen_score(cands, *padded, *padded, block_k=8, interpret=True)
        expect = ref.parzen_score_ref(cands, mus, sigmas, ln, mus, sigmas, ln)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-4)

    @settings(max_examples=20, deadline=None)
    @given(
        C=st.integers(min_value=1, max_value=200),
        Kl=st.integers(min_value=1, max_value=40),
        Kg=st.integers(min_value=1, max_value=40),
    )
    def test_property_sweep(self, C, Kl, Kg):
        from repro.kernels.parzen import parzen_score

        rng = np.random.RandomState(C * 1000 + Kl * 40 + Kg)
        cands = jnp.asarray(rng.uniform(-4, 4, C).astype(np.float32))
        l = self._mixture(rng, Kl)
        g = self._mixture(rng, Kg)
        out = parzen_score(cands, *l, *g, block_c=32, block_k=16, interpret=True)
        expect = ref.parzen_score_ref(cands, *l, *g)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-4, rtol=2e-4)


class TestParzenPacking:
    """The wrapper packs the kernel's inputs on the host in numpy and runs
    one jitted program per call."""

    def _sides(self, rng, Kl, Kg, dtype=np.float64):
        def side(k):
            ln = np.log(np.full(k, 1.0 / k)) - np.log(rng.uniform(0.05, 1.0, k))
            ln[-1] = -np.inf  # pow2 padding from ``ops.pad_pow2_vec``
            return (rng.uniform(-3, 3, k).astype(dtype),
                    rng.uniform(0.05, 1.0, k).astype(dtype), ln.astype(dtype))

        return side(Kl), side(Kg)

    @pytest.mark.parametrize(
        "C,Kl,Kg,bc,bk,C_p,K_p",
        [
            (100, 16, 40, 32, 16, 128, 48),  # candidates and components padded
            (24, 32, 8192, 256, 1024, 24, 8192),  # the live ask's shapes
            (300, 50, 7, 256, 1024, 512, 50),  # blocks clamp to C and K
        ],
    )
    def test_layout(self, C, Kl, Kg, bc, bk, C_p, K_p):
        from repro.kernels.parzen import NEG_INF, _pack

        rng = np.random.RandomState(C + Kl + Kg)
        cands = rng.uniform(-4, 4, C)
        sides = self._sides(rng, Kl, Kg)
        col, comps, block_c, block_k = _pack(cands, sides, block_c=bc, block_k=bk)
        assert (block_c, block_k) == (min(bc, C), min(bk, max(Kl, Kg)))
        assert isinstance(col, np.ndarray) and isinstance(comps, np.ndarray)
        assert col.dtype == comps.dtype == np.float32
        assert col.shape == (C_p, 1) and comps.shape == (8, K_p)
        np.testing.assert_array_equal(col[:C, 0], cands.astype(np.float32))
        assert (col[C:] == 0.0).all()
        for base, side in zip((0, 3), sides):
            k = len(side[0])
            for row, (x, fill) in enumerate(zip(side, (0.0, 1.0, NEG_INF)), start=base):
                np.testing.assert_array_equal(comps[row, :k], x.astype(np.float32))
                assert (comps[row, k:] == np.float32(fill)).all()
            assert comps[base + 2, k - 1] == -np.inf  # upstream padding kept
        assert (comps[6:] == 1.0).all()

    def test_float64_inputs_score_bit_identical_to_float32(self):
        from repro.kernels.parzen import parzen_score

        rng = np.random.RandomState(7)
        cands = rng.uniform(-4, 4, 50)
        l, g = self._sides(rng, 12, 30)
        f32 = lambda xs: [x.astype(np.float32) for x in xs]
        kw = dict(block_c=16, block_k=8, interpret=True)
        out64 = np.asarray(parzen_score(cands, *l, *g, **kw))
        out32 = np.asarray(parzen_score(*f32([cands]), *f32(l), *f32(g), **kw))
        assert out64.shape == (50,) and out64.dtype == np.float32
        assert np.isfinite(out64).all()
        np.testing.assert_array_equal(out64, out32)

    def test_one_trace_per_bucket_not_per_call(self):
        from repro.kernels.ops import trace_count
        from repro.kernels.parzen import parzen_score

        rng = np.random.RandomState(3)
        cands = rng.uniform(-4, 4, 37)
        kw = dict(block_c=8, block_k=8, interpret=True)
        before = trace_count("pallas.parzen")
        # K_p 72: raw lengths 65..72 on either side share the bucket
        for Kl, Kg in ((72, 60), (65, 72), (72, 72)):
            l, g = self._sides(rng, Kl, Kg)
            parzen_score(cands, *l, *g, **kw)
        assert trace_count("pallas.parzen") - before == 1
        for Kl, Kg in ((136, 9), (129, 136)):  # K_p 136: a new bucket
            l, g = self._sides(rng, Kl, Kg)
            parzen_score(cands, *l, *g, **kw)
        assert trace_count("pallas.parzen") - before == 2
        l, g = self._sides(rng, 70, 70)
        parzen_score(cands, *l, *g, **kw)  # back to the first bucket
        assert trace_count("pallas.parzen") - before == 2

    def test_prepare_runs_no_jax_computation(self, monkeypatch):
        """After the bucket's first call, a call touches ``jnp`` nowhere,
        makes one ``device_put`` of two float32 numpy arrays, and dispatches
        the jitted program once."""
        import types

        from repro.kernels import parzen

        rng = np.random.RandomState(11)
        cands = rng.uniform(-4, 4, 24)
        l, g = self._sides(rng, 16, 20)
        kw = dict(block_c=256, block_k=1024, interpret=True)
        warm = np.asarray(parzen.parzen_score(cands, *l, *g, **kw))

        class NoJnp:
            def __getattr__(self, name):
                raise AssertionError(f"jnp.{name} used by a scoring call")

        puts, launches = [], []

        def device_put(x, *a, **k):
            puts.append(x)
            return jax.device_put(x, *a, **k)

        def launch(*a, **k):
            launches.append(a)
            return padded(*a, **k)

        padded = parzen._parzen_padded
        monkeypatch.setattr(parzen, "jnp", NoJnp())
        monkeypatch.setattr(parzen, "jax", types.SimpleNamespace(device_put=device_put))
        monkeypatch.setattr(parzen, "_parzen_padded", launch)
        out = np.asarray(parzen.parzen_score(cands, *l, *g, **kw))
        np.testing.assert_array_equal(out, warm)
        assert len(puts) == 1 and len(launches) == 1
        assert [(x.dtype, x.shape) for x in puts[0]] == [
            (np.float32, (24, 1)), (np.float32, (8, 20))
        ]
        assert all(isinstance(x, np.ndarray) for x in puts[0])


class TestMCHypervolumeKernel:
    @pytest.mark.parametrize(
        "n,m,s,bs",
        [
            (8, 3, 256, 256),    # single sample block
            (20, 4, 1000, 256),  # pow2 point padding + non-multiple samples
            (64, 6, 2048, 512),  # many-objective (the estimator's regime)
            (3, 2, 100, 1024),   # block_s > s (clamp path)
        ],
    )
    def test_matches_ref(self, n, m, s, bs):
        from repro.kernels.hypervolume import mc_hv_counts

        rng = np.random.RandomState(n * m + s)
        pts = jnp.asarray(rng.uniform(0, 1, (n, m)).astype(np.float32))
        smp = jnp.asarray(rng.uniform(0, 1.1, (s, m)).astype(np.float32))
        excl, tot = mc_hv_counts(pts, smp, block_s=bs, interpret=True)
        excl_r, tot_r = ref.mc_hv_counts_ref(pts, smp)
        assert excl.shape == (n,)
        np.testing.assert_array_equal(np.asarray(excl), np.asarray(excl_r))
        assert float(tot) == float(tot_r)

    def test_counts_are_consistent(self):
        """Exclusive counts can never exceed the total dominated count, and a
        sample below every point is counted exactly once in total."""
        from repro.kernels.hypervolume import mc_hv_counts

        rng = np.random.RandomState(1)
        pts = jnp.asarray(rng.uniform(0.4, 0.6, (16, 5)).astype(np.float32))
        smp = jnp.asarray(rng.uniform(0, 1, (512, 5)).astype(np.float32))
        excl, tot = mc_hv_counts(pts, smp, block_s=128, interpret=True)
        assert float(jnp.sum(excl)) <= float(tot) <= smp.shape[0]

    @settings(max_examples=15, deadline=None)
    @given(
        n=st.integers(min_value=1, max_value=40),
        m=st.integers(min_value=2, max_value=7),
        s=st.integers(min_value=1, max_value=600),
    )
    def test_property_sweep(self, n, m, s):
        from repro.kernels.hypervolume import mc_hv_counts

        rng = np.random.RandomState(n * 7 + m * 601 + s)
        pts = jnp.asarray(rng.uniform(0, 1, (n, m)).astype(np.float32))
        smp = jnp.asarray(rng.uniform(0, 1.1, (s, m)).astype(np.float32))
        excl, tot = mc_hv_counts(pts, smp, block_s=128, interpret=True)
        excl_r, tot_r = ref.mc_hv_counts_ref(pts, smp)
        np.testing.assert_array_equal(np.asarray(excl), np.asarray(excl_r))
        assert float(tot) == float(tot_r)
