"""Runs with the timed path broken underneath: each fault a cell can have
must come out as not correct.  The harness's look for a chip is steered to
the CPU; the rest of the run is the benchmark's own."""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny.make_checkout(str(tmp_path_factory.mktemp("checkout")))


@pytest.fixture
def cpu(monkeypatch, checkout):
    tiny.use_checkout(monkeypatch, checkout)
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    return checkout


def _break_step(monkeypatch, broken):
    """Replace the step the trainer jits with ``broken(step, *args)``."""
    from repro.train import train_loop

    orig = train_loop._jit_on_mesh

    def jit(*a, **k):
        fn = orig(*a, **k)

        def step(*args):
            return broken(fn, *args)

        step.lower = fn.lower  # the harness reads the step program's memory
        return step

    monkeypatch.setattr(train_loop, "_jit_on_mesh", jit)


def _unchanged(fn, params, opt_state, step_no, batch):
    """A step that returns its state unchanged."""
    keep = jax.tree.map(jnp.copy, (params, opt_state))
    _, _, metrics = fn(params, opt_state, step_no, batch)
    return (*keep, metrics)


def _half_batch(fn, params, opt_state, step_no, batch):
    """Half of the batch left out, the mean taken over the rest."""
    return fn(params, opt_state, step_no, {k: v[: v.shape[0] // 2] for k, v in batch.items()})


@pytest.mark.parametrize("fault", [_unchanged, _half_batch], ids=["state_unchanged", "half_batch"])
def test_training_faults_are_not_correct(cpu, monkeypatch, fault):
    _break_step(monkeypatch, fault)
    code, line, err = tiny.run_cell("tiny-lm.short-trials", trace=0, seconds=4.0)
    assert code == 0, err
    assert line["correct"] is False, line["checks"]


def test_altered_kernel_answer_is_not_correct(cpu, monkeypatch):
    from repro.kernels import ops as kops

    op = kops.parzen_score_op
    monkeypatch.setattr(kops, "parzen_score_op", lambda *args: op(*args) + 1e-2)
    code, line, err = tiny.run_cell("tiny-tpe.live-ask", trace=0, seconds=1.0)
    assert code == 0, err
    assert line["correct"] is False and line["checks"]["score_err"]["value"] > 1e-3


def test_altered_stored_value_is_not_correct(cpu, monkeypatch):
    from repro.core.storage.inmemory import InMemoryStorage

    orig = InMemoryStorage.set_trial_state_values

    def altered(self, trial_id, state, values=None):
        if values is not None:
            values = [v + 1.0 for v in values]
        return orig(self, trial_id, state, values)

    monkeypatch.setattr(InMemoryStorage, "set_trial_state_values", altered)
    code, line, err = tiny.run_cell("tiny-tpe.live-ask", trace=0, seconds=1.0)
    assert code == 0, err
    assert line["correct"] is False and line["checks"]["stored_mismatch"]["value"] > 0


def test_stale_fit_is_not_correct(cpu, monkeypatch):
    """Each ask fitted on the history as the previous ask saw it."""
    from repro.core.samplers.tpe import TPESampler

    orig = TPESampler._trial_fit
    held: dict = {}

    def stale(self, study, trial):
        fit = orig(self, study, trial)
        if held.get("trial") != trial.number:
            held["stale"], held["fresh"], held["trial"] = held.get("fresh", fit), fit, trial.number
        return held["stale"]

    monkeypatch.setattr(TPESampler, "_trial_fit", stale)
    code, line, err = tiny.run_cell("tiny-tpe.live-ask", trace=0, seconds=1.0)
    assert code == 0, err
    checks = line["checks"]
    assert line["correct"] is False and checks["fit_err"]["value"] > checks["fit_err"]["limit"], checks


def test_choice_not_by_score_is_not_correct(cpu, monkeypatch):
    """Each ask suggests the candidate it scores lowest."""
    from repro.core.samplers.tpe import TPESampler

    orig = TPESampler._score
    monkeypatch.setattr(TPESampler, "_score", lambda self, *a: -orig(self, *a))
    code, line, err = tiny.run_cell("tiny-tpe.live-ask", trace=0, seconds=1.0)
    assert code == 0, err
    checks = line["checks"]
    assert checks["score_err"]["value"] <= checks["score_err"]["limit"], checks
    assert line["correct"] is False and checks["choice_gap"]["value"] > checks["choice_gap"]["limit"], checks
