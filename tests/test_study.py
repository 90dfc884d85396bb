"""Study API: optimize loop, ask/tell, distributed workers, fault tolerance,
dashboard, importances."""

import math
import os

import numpy as np
import pytest

import repro.core as hpo
from repro.core.frozen import TrialState


def test_optimize_minimize_and_best():
    s = hpo.create_study(sampler=hpo.RandomSampler(seed=0))
    s.optimize(lambda t: (t.suggest_float("x", -5, 5) - 1) ** 2, n_trials=50)
    assert s.best_value < 1.0
    assert abs(s.best_params["x"] - 1.0) < 1.5
    assert s.best_trial.state == TrialState.COMPLETE


def test_optimize_maximize():
    s = hpo.create_study(direction="maximize", sampler=hpo.RandomSampler(seed=0))
    s.optimize(lambda t: -(t.suggest_float("x", -5, 5) ** 2), n_trials=30)
    assert s.best_value > -1.5


def test_failed_trials_recorded_and_raised():
    s = hpo.create_study()

    def obj(trial):
        raise RuntimeError("boom")

    with pytest.raises(RuntimeError):
        s.optimize(obj, n_trials=1)
    assert s.trials[0].state == TrialState.FAIL

    # catch= suppresses
    s.optimize(obj, n_trials=2, catch=(RuntimeError,))
    assert len(s.trials) == 3


def test_nan_objective_fails_trial():
    s = hpo.create_study()
    s.optimize(lambda t: float("nan"), n_trials=1, catch=(Exception,))
    assert s.trials[0].state == TrialState.FAIL


def test_ask_tell():
    s = hpo.create_study(sampler=hpo.TPESampler(seed=0))
    for _ in range(10):
        t = s.ask()
        x = t.suggest_float("x", 0, 1)
        s.tell(t, x * x)
    assert len(s.trials) == 10
    assert s.best_value >= 0


def test_tell_pruned_and_fail_states():
    s = hpo.create_study()
    t = s.ask()
    t.report(1.0, 0)
    s.tell(t, state=TrialState.PRUNED)
    assert s.trials[0].state == TrialState.PRUNED
    t2 = s.ask()
    s.tell(t2, state=TrialState.FAIL)
    assert s.trials[1].state == TrialState.FAIL


def test_n_jobs_threaded():
    s = hpo.create_study(sampler=hpo.RandomSampler(seed=0))
    s.optimize(lambda t: t.suggest_float("x", 0, 1), n_trials=24, n_jobs=4)
    assert len(s.trials) == 24
    assert sorted(t.number for t in s.trials) == list(range(24))


def test_timeout_stops_loop():
    import time

    s = hpo.create_study()

    def slow(trial):
        time.sleep(0.02)
        return 1.0

    s.optimize(slow, timeout=0.2)
    assert 1 <= len(s.trials) <= 30


def test_stop_from_callback():
    s = hpo.create_study()

    def cb(study, trial):
        if trial.number >= 4:
            study.stop()

    s.optimize(lambda t: 0.0, n_trials=100, callbacks=[cb])
    assert len(s.trials) <= 6


def test_multiobjective_pareto():
    s = hpo.create_study(directions=["minimize", "minimize"])

    def obj(t):
        x = t.suggest_float("x", 0, 1)
        return x, 1 - x

    s.optimize(obj, n_trials=20)
    front = s.best_trials
    assert len(front) == 20  # all on the Pareto front of (x, 1-x)


def test_trials_dataframe_export():
    s = hpo.create_study()
    s.optimize(lambda t: t.suggest_float("x", 0, 1), n_trials=5)
    rows = s.trials_dataframe()
    assert len(rows) == 5
    assert {"number", "state", "value", "params_x"} <= set(rows[0])


def test_study_user_attrs_and_system_attrs(tmp_sqlite):
    s = hpo.create_study(study_name="attrs", storage=tmp_sqlite)
    s.set_user_attr("dataset", "svhn")
    s.set_system_attr("version", 2)
    s2 = hpo.load_study("attrs", tmp_sqlite)
    assert s2.user_attrs["dataset"] == "svhn"
    assert s2.system_attrs["version"] == 2


def test_distributed_processes_sqlite(tmp_path):
    url = f"sqlite:///{tmp_path}/dist.db"
    hpo.create_study(study_name="dist", storage=url)

    dur = hpo.run_workers(
        3, url, "dist", _sphere, n_trials_per_worker=8,
    )
    s = hpo.load_study("dist", url)
    assert len(s.trials) == 24
    assert sorted(t.number for t in s.trials) == list(range(24))
    assert s.best_value < 10.0


def test_held_accelerator_check_finds_its_private_jax_query():
    # run_workers reads this private symbol in any process that imported jax;
    # an upgrade that moves it must fail here, not in every run_workers call
    pytest.importorskip("jax")
    from jax._src import xla_bridge

    assert callable(xla_bridge.backends_are_initialized)


def test_run_workers_refuses_while_this_process_holds_an_accelerator(tmp_path, monkeypatch):
    jax = pytest.importorskip("jax")
    from repro.core import distributed

    jax.devices()  # initialise this process's backend (the CPU here)
    assert distributed._held_accelerator() is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    url = f"sqlite:///{tmp_path}/held.db"
    hpo.create_study(study_name="held", storage=url)
    with pytest.raises(RuntimeError, match="holds the tpu device"):
        hpo.run_workers(2, url, "held", _sphere, n_trials_per_worker=1)
    assert len(hpo.load_study("held", url).trials) == 0  # no worker started


def test_distributed_processes_journal(tmp_path):
    url = f"journal://{tmp_path}/dist.journal"
    hpo.create_study(study_name="dist", storage=url)
    hpo.run_workers(3, url, "dist", _sphere, n_trials_per_worker=6)
    s = hpo.load_study("dist", url)
    assert len(s.trials) == 18
    assert sorted(t.number for t in s.trials) == list(range(18))


def _sphere(trial):
    return sum(trial.suggest_float(f"x{i}", -3, 3) ** 2 for i in range(3))


def test_retry_failed_trial_callback():
    s = hpo.create_study()
    cb = hpo.RetryFailedTrialCallback(max_retry=1)

    calls = {"n": 0}

    def flaky(trial):
        trial.suggest_float("x", 0, 1)
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("node died")
        return 1.0

    s.optimize(flaky, n_trials=2, catch=(RuntimeError,), callbacks=[cb])
    states = [t.state for t in s.trials]
    assert TrialState.FAIL in states
    assert TrialState.COMPLETE in states
    retried = [t for t in s.trials if t.user_attrs.get("retry_of") is not None]
    assert retried, "failed trial must be re-enqueued"


def test_importances_and_dashboard(tmp_path):
    s = hpo.create_study(sampler=hpo.RandomSampler(seed=0))

    def obj(t):
        x = t.suggest_float("important", 0, 1)
        y = t.suggest_float("noise", 0, 1)
        return 10 * x + 0.01 * y

    s.optimize(obj, n_trials=60)
    imps = hpo.param_importances(s)
    assert imps["important"] > imps["noise"]
    sp = hpo.spearman_importances(s)
    assert sp["important"] > sp["noise"]

    html = hpo.render_dashboard(s)
    assert "<svg" in html and "important" in html
    out = hpo.save_dashboard(s, str(tmp_path / "dash.html"))
    assert os.path.getsize(out) > 1000


def test_heartbeat_failover_via_study():
    st = hpo.InMemoryStorage()
    s = hpo.create_study(study_name="hb", storage=st)
    s.failed_trial_grace = 0.01
    tid = st.create_new_trial(s._study_id)
    st.record_heartbeat(tid)
    import time

    time.sleep(0.05)
    assert s.fail_stale_trials() == [tid]
    assert st.get_trial(tid).state == TrialState.FAIL
    # retry re-enqueues the params of failed trials
    n = s.retry_failed_trials()
    assert n == 1
    waiting = s.get_trials(states=(TrialState.WAITING,))
    assert len(waiting) == 1
