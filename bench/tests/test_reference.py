"""The plain references agree with the program at smoke size on the CPU,
when the program computes in the precision the reference does."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402
from bench import harness  # noqa: E402
from bench.reference import parzen, sha, smollm, tpe  # noqa: E402

CFG = tiny.TINY_LM


@pytest.fixture(scope="module")
def drv():
    return harness.load_module(os.path.join(harness.BENCH, "drivers", "hpo_trials.py"))


def _program_params(drv, seed):
    from repro.models import init_model_params

    model = drv.model_config(CFG)
    flat, _ = jax.tree_util.tree_flatten_with_path(init_model_params(model, jax.random.PRNGKey(seed)))
    return {jax.tree_util.keystr(p): v for p, v in flat}


def test_weights_match_the_programs_draw(drv):
    seed = harness.sub_seed(2**31 + 5, "weights:0")
    prog = _program_params(drv, seed)
    ref = smollm.init_params(CFG, seed)
    assert set(prog) == set(ref)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(prog[k]), np.asarray(ref[k]), err_msg=k)


def test_loss_matches_program_in_float32(drv):
    from repro.models import loss_fn

    model = dataclasses.replace(drv.model_config(CFG), compute_dtype="float32")
    seed = 7
    params = _program_params(drv, seed)
    data = drv.Tokens(3, CFG["batch"], CFG["seq"], CFG["vocab_size"], 1.1, lambda n: None)
    b = data.batch_at(0)
    from repro.models import init_model_params

    with jax.default_matmul_precision("highest"):
        want, _ = loss_fn(init_model_params(model, jax.random.PRNGKey(seed)), model,
                          {k: jnp.asarray(v) for k, v in b.items()})
        got = smollm.loss(params, jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]), CFG)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))


def test_first_steps_match_program_in_float32(drv):
    """Losses, first gradient and the change after three AdamW steps, of the
    program's trainer (float32 compute) against the reference."""
    import contextlib

    from repro.launch.mesh import make_auto_mesh
    from repro.train import TrainConfig, Trainer

    model = dataclasses.replace(drv.model_config(CFG), compute_dtype="float32")
    tcfg = TrainConfig(lr=3e-3, warmup_steps=2, total_steps=6, weight_decay=0.05,
                       eval_every=2, checkpoint_every=10**9, seed=11)
    data = drv.Tokens(5, CFG["batch"], CFG["seq"], CFG["vocab_size"], 1.1,
                      lambda n: contextlib.nullcontext())
    probe = drv.StepProbe(lambda n: contextlib.nullcontext())
    rec = drv.TrialRecord(0, tcfg, 3)
    probe.local.rec = rec
    try:
        with jax.default_matmul_precision("highest"):
            Trainer(model, tcfg, data, mesh=make_auto_mesh((1, 1), ("data", "model"),
                                                           devices=jax.devices()[:1])).run()
    finally:
        probe.close()
    got = drv.program_steps(rec)
    ref = drv.reference_steps(CFG, rec, data)
    g = drv.gaps(got, ref)
    assert g["loss_gap"] < 1e-5 and g["grad_gap"] < 1e-3 and g["update_gap"] < 1e-3, g


def test_parzen_reference_is_the_programs_numpy_scorer():
    from repro.core.samplers.tpe import _score_numpy

    rng = np.random.default_rng(0)
    c = rng.uniform(-5, 5, 24)
    lo = [rng.uniform(-5, 5, 32), rng.uniform(0.1, 2, 32), np.log(np.full(32, 1 / 26))]
    hi = [rng.uniform(-5, 5, 1024), rng.uniform(0.1, 2, 1024), np.log(np.full(1024, 1 / 900))]
    lo[2][26:] = -np.inf
    hi[2][900:] = -np.inf
    np.testing.assert_array_equal(parzen.score(c, *lo, *hi), _score_numpy(c, *lo, *hi))


@pytest.mark.parametrize("n", [12, 300, 5000])
def test_tpe_fit_is_the_programs(n):
    """The reference's split and Parzen fit give the program's mixtures: the
    same components, and log-densities equal to rounding."""
    from repro.core.samplers.tpe import _ParzenEstimator, default_gamma, default_weights

    rng = np.random.default_rng(n)
    low, high = -5.12, 5.12
    xs = rng.uniform(low, high, n)
    losses = rng.normal(size=n)
    order = np.argsort(losses, kind="stable")
    k = default_gamma(n)
    w = default_weights(n)
    grid = np.linspace(low, high, 257)
    for side, ref in zip((order[:k], order[k:]), tpe.fit(xs, losses, low, high)):
        est = _ParzenEstimator(xs[side], low, high, w[side])
        np.testing.assert_allclose(est.mus, ref[0], rtol=0, atol=0)
        np.testing.assert_allclose(est.sigmas, ref[1], rtol=1e-12)
        got = parzen.mixture_log_pdf(grid, est.mus, est.sigmas, est._log_norm)
        want = parzen.mixture_log_pdf(grid, *ref)
        assert parzen.score_error(got, want) < 1e-8


@pytest.mark.parametrize("seed", range(4))
def test_sha_decisions_match_the_programs_legacy_pruner(seed):
    """Random report sequences: the paper's Algorithm 1 as written here and
    the program's frozen scalar pruner decide alike."""
    import repro.core as hpo
    from repro.core.frozen import TrialState
    from repro.core.pruners._legacy import LegacySuccessiveHalvingPruner

    rng = np.random.default_rng(seed)
    study = hpo.create_study()
    storage, sid = study._storage, study._study_id
    legacy = LegacySuccessiveHalvingPruner(2, 3)
    seen: dict = {}
    for _ in range(12):
        tid = storage.create_new_trial(sid)
        for step in range(2, 20, 2):
            value = float(rng.normal()) if rng.random() > 0.05 else float("nan")
            storage.set_trial_intermediate_value(tid, step, value)
            want = legacy.prune(study, storage.get_trial(tid))
            peers = [v for n, v in seen.get(step, [])]
            assert sha.prunes(value, step, peers, 2, 3) == want
            seen.setdefault(step, []).append((tid, value))
            if want:
                break
        storage.set_trial_state_values(tid, TrialState.PRUNED)
