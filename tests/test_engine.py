"""Device-resident engine: policy unit tests + numpy/jit/Pallas parity.

Covers the shared engine policy in ``kernels/ops.py`` (pow2 padding, trace
registry, ``resolve_engine``), randomized agreement between the numpy, jitted
and Pallas(interpret) paths for Parzen scoring, dominance and hypervolume
contributions, pinned trace counts proving pow2 bucketing bounds retracing,
and the loud-fallback contract (``sampler.engine_fallbacks`` counter +
once-per-reason log) when a requested device engine cannot run.
"""

import logging

import numpy as np
import pytest

import repro.core as hpo
from repro.core import moo, telemetry
from repro.core.frozen import TrialState
from repro.core.samplers.tpe import _ParzenEstimator, _pad_est, _score_numpy
from repro.core.storage import InMemoryStorage
from repro.kernels import ops as kops

jax = pytest.importorskip("jax")


# -- shared policy helpers (kernels/ops.py) -----------------------------------------


class TestOpsPolicy:
    def test_pad_pow2_len(self):
        assert kops.pad_pow2_len(0) == 8
        assert kops.pad_pow2_len(1) == 8
        assert kops.pad_pow2_len(8) == 8
        assert kops.pad_pow2_len(9) == 16
        assert kops.pad_pow2_len(1000) == 1024
        assert kops.pad_pow2_len(3, min_pad=2) == 4

    def test_pad_pow2_vec(self):
        v = np.arange(5, dtype=float)
        out = kops.pad_pow2_vec(v, -np.inf)
        assert out.shape == (8,)
        assert np.array_equal(out[:5], v)
        assert np.all(np.isneginf(out[5:]))
        # already a pow2 bucket: returned untouched (same object)
        v8 = np.arange(8, dtype=float)
        assert kops.pad_pow2_vec(v8, 0.0) is v8

    def test_pad_pow2_rows(self):
        A = np.arange(6, dtype=float).reshape(3, 2)
        out = kops.pad_pow2_rows(A, np.inf)
        assert out.shape == (8, 2)
        assert np.array_equal(out[:3], A)
        assert np.all(np.isinf(out[3:]))

    def test_validate_engine(self):
        for eng in ("auto", "numpy", "jax", "pallas"):
            assert kops.validate_engine(eng) == eng
        with pytest.raises(ValueError):
            kops.validate_engine("cuda")

    def test_resolve_engine(self):
        # explicit engines pass through regardless of work
        assert kops.resolve_engine("numpy", 10**9, 1) == "numpy"
        assert kops.resolve_engine("jax", 0, 10**9) == "jax"
        assert kops.resolve_engine("pallas", 0, 10**9) == "pallas"
        # auto: numpy below the threshold, device above it
        assert kops.resolve_engine("auto", 100, 1000) == "numpy"
        above = kops.resolve_engine("auto", 2000, 1000)
        assert above in ("jax", "pallas")
        # ceiling caps auto off-TPU (memory-bound reductions)
        if jax.default_backend() != "tpu":
            assert kops.resolve_engine("auto", 2000, 1000, ceiling=1500) == "numpy"

    def test_trace_registry(self):
        kops.reset_traces("test.key")
        assert kops.trace_count("test.key") == 0
        kops.bump_trace("test.key")
        kops.bump_trace("test.key")
        assert kops.trace_count("test.key") == 2
        kops.reset_traces("test.key")
        assert kops.trace_count("test.key") == 0


# -- Parzen scoring parity ----------------------------------------------------------


def _mk_est(rng, n_obs, low=-3.0, high=3.0):
    obs = rng.uniform(low, high, n_obs)
    w = rng.uniform(0.5, 1.0, n_obs)
    return _ParzenEstimator(obs, low, high, w, True, 1.0, True)


def _sampler(engine):
    return hpo.TPESampler(seed=0, engine=engine)


class TestParzenParity:
    @pytest.mark.parametrize("n_below,n_above", [(3, 20), (25, 200), (7, 8)])
    def test_numpy_jax_pallas_agree(self, n_below, n_above):
        rng = np.random.RandomState(n_below * 100 + n_above)
        l_est, g_est = _mk_est(rng, n_below), _mk_est(rng, n_above)
        cands = rng.uniform(-3, 3, 64)
        ref = _sampler("numpy")._score_inner(l_est, g_est, cands)
        for engine in ("jax", "pallas"):
            out = _sampler(engine)._score_inner(l_est, g_est, cands)
            np.testing.assert_allclose(out, ref, atol=2e-4, rtol=1e-4)

    def test_pow2_padding_is_invisible(self):
        """-inf log_norm fills contribute exp(-inf)=0: padded == unpadded."""
        rng = np.random.RandomState(7)
        l_est, g_est = _mk_est(rng, 5), _mk_est(rng, 13)
        cands = rng.uniform(-3, 3, 32)
        padded = _pad_est(l_est)
        n = len(l_est.mus)  # 5 observations + the wide prior component
        assert len(padded[0]) == 8 and np.isneginf(padded[2][n:]).all()
        direct = _score_numpy(
            cands,
            l_est.mus, l_est.sigmas, l_est._log_norm,
            g_est.mus, g_est.sigmas, g_est._log_norm,
        )
        via_pad = _score_numpy(cands, *padded, *_pad_est(g_est))
        np.testing.assert_allclose(via_pad, direct, atol=1e-12)

    def test_score_table_matches_direct_scoring(self):
        """The device score table is the acquisition on a dense grid; interp
        at arbitrary candidates stays within the magic_clip smoothness
        bound (~1e-4 in log space)."""
        rng = np.random.RandomState(3)
        low, high = -3.0, 3.0
        l_est, g_est = _mk_est(rng, 30), _mk_est(rng, 400)
        s = _sampler("jax")
        cache = {}
        for _ in range(2):  # table builds on the second score at one version
            s._maybe_build_table(cache, "x", l_est, g_est, low, high)
        xs, ys = cache[("x", "table")]
        assert len(xs) == kops.SCORE_TABLE_SIZE
        np.testing.assert_allclose(
            ys, s._score_inner(l_est, g_est, xs), atol=2e-4, rtol=1e-4
        )
        cands = rng.uniform(low, high, 256)
        direct = _sampler("numpy")._score_inner(l_est, g_est, cands)
        np.testing.assert_allclose(np.interp(cands, xs, ys), direct, atol=5e-3)

    def test_engines_pick_same_candidates_end_to_end(self):
        results = {}
        for engine in ("numpy", "jax", "pallas"):
            s = hpo.create_study(sampler=hpo.TPESampler(seed=11, engine=engine))
            s.optimize(lambda t: t.suggest_float("x", -4, 4) ** 2, n_trials=14)
            results[engine] = [t.params["x"] for t in s.trials]
        np.testing.assert_allclose(results["jax"], results["numpy"], rtol=1e-5)
        np.testing.assert_allclose(results["pallas"], results["numpy"], rtol=1e-5)


# -- dominance parity ---------------------------------------------------------------


class TestDominanceParity:
    @pytest.mark.parametrize("n,m", [(17, 2), (33, 3), (64, 5)])
    def test_numpy_jax_agree(self, n, m):
        rng = np.random.RandomState(n * m)
        V = rng.randn(n, m)
        # duplicated + dominated rows exercise ties
        V[3] = V[0]
        V[5] = V[1] + 1.0
        ref = moo.dominance_matrix(V)
        assert np.array_equal(moo.dominance_matrix(V, engine="jax"), ref)
        ranks_np = moo.nondomination_ranks(V)
        ranks_jax = moo.nondomination_ranks(V, engine="jax")
        assert np.array_equal(ranks_np, ranks_jax)

    def test_nan_rows_agree(self):
        rng = np.random.RandomState(5)
        V = rng.randn(21, 3)
        V[2, 1] = np.nan
        V[9] = np.nan
        assert np.array_equal(
            moo.dominance_matrix(V, engine="jax"), moo.dominance_matrix(V)
        )

    def test_both_orientations_agree(self):
        """Maximize columns are handled upstream by loss_matrix: parity must
        hold on the sign-flipped matrix too."""
        from repro.core.frozen import StudyDirection

        rng = np.random.RandomState(8)
        V = rng.randn(25, 2)
        for dirs in (
            [StudyDirection.MINIMIZE, StudyDirection.MAXIMIZE],
            [StudyDirection.MAXIMIZE, StudyDirection.MAXIMIZE],
        ):
            L = moo.loss_matrix(V, dirs)
            assert np.array_equal(
                moo.pareto_front_mask(L, engine="jax"), moo.pareto_front_mask(L)
            )


# -- hypervolume parity -------------------------------------------------------------


class TestHypervolumeParity:
    def test_mc_engines_agree(self):
        rng = np.random.RandomState(0)
        pts = rng.rand(24, 6)
        ref = np.full(6, 1.1)
        outs = {}
        for engine in ("numpy", "jax", "pallas"):
            est = moo.HypervolumeEstimator(method="mc", n_samples=4096, engine=engine)
            outs[engine] = (est.hypervolume(pts, ref), est.contributions(pts, ref))
        for engine in ("jax", "pallas"):
            assert abs(outs[engine][0] - outs["numpy"][0]) < 1e-4
            np.testing.assert_allclose(outs[engine][1], outs["numpy"][1], atol=1e-5)

    def test_mc_tracks_exact(self):
        rng = np.random.RandomState(1)
        pts = rng.rand(30, 3)
        ref = np.full(3, 1.1)
        est = moo.HypervolumeEstimator(method="mc", n_samples=100_000)
        hv_exact = moo.hypervolume(pts, ref)
        assert abs(est.hypervolume(pts, ref) - hv_exact) / hv_exact < 0.05
        front = pts[moo.pareto_front_mask(pts)]
        c_exact = moo.hypervolume_contributions(front, ref)
        c_mc = est.contributions(front, ref)
        np.testing.assert_allclose(c_mc, c_exact, atol=5e-3)

    def test_auto_method_switch(self):
        est = moo.HypervolumeEstimator()
        assert est._use_exact(4) and not est._use_exact(5)
        # m <= 4 via the estimator is bit-identical to the exact function
        rng = np.random.RandomState(2)
        pts = rng.rand(12, 3)
        ref = np.full(3, 1.1)
        assert est.hypervolume(pts, ref) == moo.hypervolume(pts, ref)

    def test_dominated_and_outside_points_contribute_zero(self):
        est = moo.HypervolumeEstimator(method="mc", n_samples=8192)
        pts = np.asarray([
            [0.2, 0.2, 0.2, 0.2, 0.2],
            [0.5, 0.5, 0.5, 0.5, 0.5],  # dominated by row 0
            [2.0, 2.0, 2.0, 2.0, 2.0],  # outside the reference box
        ])
        ref = np.ones(5)
        contrib = est.contributions(pts, ref)
        assert contrib[0] > 0.0
        assert contrib[1] == 0.0  # exclusive region of a dominated point is empty
        assert contrib[2] == 0.0


# -- pinned trace counts ------------------------------------------------------------


class TestTraceBounds:
    def test_parzen_kernel_traces_bounded(self):
        from repro.kernels.parzen import parzen_score

        rng = np.random.RandomState(0)
        cands = rng.uniform(-3, 3, 512).astype(np.float32)
        before = kops.trace_count("pallas.parzen")
        for n in range(20, 30):  # one pow2 bucket: at most one fresh trace
            est = _mk_est(np.random.RandomState(n), n)
            parzen_score(cands, *_pad_est(est), *_pad_est(est), interpret=True)
        assert kops.trace_count("pallas.parzen") - before <= 1

    def test_mc_hv_kernel_traces_bounded(self):
        from repro.kernels.hypervolume import mc_hv_counts

        rng = np.random.RandomState(0)
        samples = rng.rand(2048, 4).astype(np.float32)
        before = kops.trace_count("pallas.mc_hv")
        for n in range(17, 27):  # all pad to 32 points
            mc_hv_counts(rng.rand(n, 4).astype(np.float32), samples, interpret=True)
        assert kops.trace_count("pallas.mc_hv") - before <= 1

    def test_gemm_scorer_traces_bounded(self):
        import repro.core.samplers.tpe as tpe_mod

        tpe_mod._jax_gemm_score = None  # fresh jit cache for a clean count
        kops.reset_traces("tpe.joint")
        sampler = hpo.TPESampler(seed=2, multivariate=True, engine="jax",
                                 n_startup_trials=8)
        study = hpo.create_study(sampler=sampler)

        def obj(t):
            x = t.suggest_float("x", -3, 3)
            c = t.suggest_categorical("c", ["a", "b"])
            return x * x + (0.0 if c == "a" else 0.5)

        study.optimize(obj, n_trials=12)
        for _ in range(6):  # observation count sweeps within pow2 buckets
            wave = study.ask(4)
            study.tell_batch([(t, obj(t)) for t in wave])
        assert 0 < kops.trace_count("tpe.joint") <= 6


# -- loud fallback ------------------------------------------------------------------


class TestEngineFallback:
    def test_fallback_counts_and_logs_once(self, monkeypatch, caplog):
        from repro.core.log import reset_once

        monkeypatch.setattr(kops, "_jax_probe", False)  # jax "not importable"
        telemetry.enable()
        try:
            telemetry.reset()
            reset_once()
            sampler = hpo.TPESampler(seed=0, engine="jax", n_startup_trials=3)
            study = hpo.create_study(sampler=sampler)
            with caplog.at_level(logging.WARNING, logger="repro.core.samplers.tpe"):
                study.optimize(lambda t: t.suggest_float("x", -3, 3) ** 2, n_trials=8)
            assert telemetry.counter("sampler.engine_fallbacks").value >= 1
            warns = [r for r in caplog.records if "downgraded to numpy" in r.message]
            assert len(warns) == 1  # once per (sampler, reason), not per ask
            # the study still optimizes on the numpy path
            assert np.isfinite(study.best_value)
        finally:
            telemetry.disable()

    def test_tpe_device_error_raises(self, monkeypatch):
        """A failing device call surfaces; it never downgrades to numpy."""
        import repro.core.samplers.tpe as tpe_mod

        def broken(*args):
            raise RuntimeError("device call failed")

        monkeypatch.setattr(tpe_mod, "_get_jax_score", lambda: broken)
        telemetry.enable()
        try:
            telemetry.reset()
            sampler = hpo.TPESampler(seed=0, engine="jax", n_startup_trials=3)
            study = hpo.create_study(sampler=sampler)
            study.optimize(lambda t: t.suggest_float("x", -3, 3) ** 2, n_trials=3)
            trial = study.ask()
            with pytest.raises(RuntimeError, match="device call failed"):
                trial.suggest_float("x", -3, 3)
            assert telemetry.counter("sampler.engine_fallbacks").value == 0
        finally:
            telemetry.disable()

    def test_hypervolume_device_error_raises(self, monkeypatch):
        def broken(*args):
            raise RuntimeError("device call failed")

        monkeypatch.setattr(moo, "_get_jax_mc_counts", lambda: broken)
        est = moo.HypervolumeEstimator(method="mc", engine="jax")
        pts = np.random.RandomState(0).rand(6, 5)
        with pytest.raises(RuntimeError, match="device call failed"):
            est.hypervolume(pts, np.full(5, 1.1))

    def test_mixed_categorical_groups_keep_device_path(self):
        """Regression: categorical dims used to silently disable the joint
        device scorer; the gemm one-hot encoding keeps it on with zero
        fallbacks."""
        import repro.core.samplers.tpe as tpe_mod

        telemetry.enable()
        try:
            telemetry.reset()
            tpe_mod._jax_gemm_score = None
            kops.reset_traces("tpe.joint")
            sampler = hpo.TPESampler(seed=1, multivariate=True, engine="jax",
                                     n_startup_trials=5)
            study = hpo.create_study(sampler=sampler)

            def obj(t):
                x = t.suggest_float("x", -3, 3)
                c = t.suggest_categorical("c", ["a", "b", "cc"])
                return x * x + {"a": 0.0, "b": 1.0, "cc": 2.0}[c]

            study.optimize(obj, n_trials=8)
            wave = study.ask(6)
            study.tell_batch([(t, obj(t)) for t in wave])
            assert kops.trace_count("tpe.joint") >= 1  # device path ran
            assert telemetry.counter("sampler.engine_fallbacks").value == 0
        finally:
            telemetry.disable()


# -- engine plumbing ----------------------------------------------------------------


class TestEnginePlumbing:
    def test_study_engine_kwarg_reaches_default_sampler(self):
        s = hpo.create_study(engine="numpy")
        assert s.sampler._engine == "numpy"
        with pytest.raises(ValueError):
            hpo.create_study(study_name="bad-engine", engine="cuda")

    def test_explicit_sampler_keeps_its_engine(self):
        s = hpo.create_study(sampler=hpo.TPESampler(engine="numpy"), engine="jax")
        assert s.sampler._engine == "numpy"

    def test_jit_scoring_alias(self):
        assert hpo.TPESampler(jit_scoring=True)._engine == "jax"
        assert hpo.TPESampler()._engine == "auto"
        assert hpo.NSGAIISampler(engine="numpy")._engine == "numpy"


# -- WAITING index (Study.ask fast path) --------------------------------------------


class TestWaitingIndex:
    def test_index_matches_scan(self):
        storage = InMemoryStorage()
        study = hpo.create_study(storage=storage)
        for i in range(5):
            study.enqueue_trial({"x": float(i)})
        trial = study.ask()  # claims the oldest enqueued trial
        trial.suggest_float("x", 0, 10)

        waiting = storage.get_all_trials(
            study._study_id, deepcopy=False, states=(TrialState.WAITING,)
        )
        scan = [
            t for t in storage.get_all_trials(study._study_id, deepcopy=False)
            if t.state == TrialState.WAITING
        ]
        assert [t.number for t in waiting] == [t.number for t in scan]
        assert len(waiting) == 4
        # the mixed-state query still takes the scan path and stays consistent
        both = storage.get_all_trials(
            study._study_id, deepcopy=False,
            states=(TrialState.WAITING, TrialState.RUNNING),
        )
        assert len(both) == 5

    def test_enqueued_order_preserved(self):
        """optimize() claims enqueued trials oldest-first through the
        WAITING index and replays their fixed params."""
        study = hpo.create_study()
        for i in range(3):
            study.enqueue_trial({"x": float(i)})
        study.optimize(lambda t: t.suggest_float("x", 0, 10), n_trials=3)
        assert [t.values[0] for t in study.trials] == [0.0, 1.0, 2.0]
