"""The trainer's program cache: trials that differ only in their settings
share one compiled step and initialiser, and never each other's settings."""

import dataclasses
import os
import sys
import threading

import jax
import numpy as np
import pytest

from repro import configs
from repro.core import telemetry
from repro.launch.mesh import make_auto_mesh
from repro.train import SyntheticLM, TrainConfig, Trainer, train_loop

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

#: two trials' settings: every setting that reaches the step differs
FIRST = TrainConfig(lr=1e-3, warmup_steps=0, weight_decay=0.1, total_steps=3, eval_every=3)
SECOND = TrainConfig(lr=4e-3, warmup_steps=2, weight_decay=0.01, total_steps=4, eval_every=2,
                     seed=1)


@pytest.fixture(scope="module")
def cfg():
    return configs.get_smoke_config("smollm-135m")


@pytest.fixture(scope="module")
def mesh():
    return make_auto_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])


class Compiles:
    """Backend compiles of the train step, counted by ``jax.monitoring``."""

    def __init__(self):
        self.n = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, fun_name="", **kwargs):
        if self.on and event == BACKEND_COMPILE and "train_step" in fun_name:
            self.n += 1


@pytest.fixture(scope="module")
def compiles():
    return Compiles()


@pytest.fixture
def counted(compiles):
    """A fresh cache, the registry on and compiles counted."""
    train_loop._programs.clear()
    telemetry.enable()
    telemetry.reset()
    compiles.n, compiles.on = 0, True
    yield compiles
    compiles.on = False
    telemetry.disable()
    telemetry.reset()


def _counters() -> dict:
    return telemetry.snapshot()["counters"]


def _run(cfg, tcfg, mesh, batch=2, seq=32):
    return Trainer(cfg, tcfg, SyntheticLM(cfg, batch, seq), mesh=mesh).run()


@pytest.fixture(scope="module")
def runs(cfg, mesh, compiles):
    """The two trials one after another on one cache, each alone on an
    empty cache, and what each counted."""
    out = {}
    train_loop._programs.clear()
    telemetry.enable()
    telemetry.reset()
    compiles.n, compiles.on = 0, True
    try:
        out["first"] = _run(cfg, FIRST, mesh)
        out["second"] = _run(cfg, SECOND, mesh)
        out["compiles"] = compiles.n
        out["counters"] = _counters()
        for name, tcfg in (("first", FIRST), ("second", SECOND)):
            train_loop._programs.clear()
            out[f"{name}_alone"] = _run(cfg, tcfg, mesh)
    finally:
        compiles.on = False
        telemetry.disable()
        telemetry.reset()
        train_loop._programs.clear()
    return out


def test_trials_of_equal_shape_compile_the_step_once(runs):
    assert runs["compiles"] == 1
    c = runs["counters"]
    for kind in ("step", "init"):
        assert c[f"train.program_cache.miss.{kind}"] == 1
        assert c[f"train.program_cache.hit.{kind}"] == 1


@pytest.mark.parametrize("name", ["first", "second"])
def test_a_cached_program_trains_with_its_own_trials_settings(runs, name):
    shared, alone = runs[name], runs[f"{name}_alone"]
    assert shared["losses"] == alone["losses"]
    for a, b in zip(jax.tree.leaves(shared["params"]), jax.tree.leaves(alone["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_two_trials_train_differently(runs):
    a, b = jax.tree.leaves(runs["first"]["params"]), jax.tree.leaves(runs["second"]["params"])
    assert any(not np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(a, b))


@pytest.mark.parametrize("change", ["config", "batch", "seq"])
def test_another_config_or_batch_shape_misses(cfg, mesh, counted, change):
    """Another config is another program; another batch shape is the same
    jitted step, which compiles again for it."""
    _run(cfg, FIRST, mesh)
    if change == "config":
        _run(dataclasses.replace(cfg, d_ff=cfg.d_ff * 2), FIRST, mesh)
    else:
        _run(cfg, FIRST, mesh, **{change: 4 if change == "batch" else 16})
    c = _counters()
    if change == "config":
        assert c["train.program_cache.miss.step"] == 2 and "train.program_cache.hit.step" not in c
        assert c["train.program_cache.miss.init"] == 2
    else:
        assert c["train.program_cache.miss.step"] == 1 and c["train.program_cache.hit.step"] == 1
        assert c["train.program_cache.miss.init"] == 1
    assert counted.n == 2


def test_jit_on_mesh_is_called_once_per_run(cfg, mesh, counted, monkeypatch):
    """The seam a wrapper of the step sees (as the benchmark's probe does):
    one call per run, each returning the cached program."""
    orig = train_loop._jit_on_mesh
    returned = []

    def counting(*args, **kwargs):
        returned.append(orig(*args, **kwargs))
        return returned[-1]

    monkeypatch.setattr(train_loop, "_jit_on_mesh", counting)
    _run(cfg, FIRST, mesh)
    _run(cfg, SECOND, mesh)
    assert len(returned) == 2 and returned[0] is returned[1]


def test_runs_without_a_mesh_share_the_step(cfg, counted):
    _run(cfg, FIRST, None)
    _run(cfg, SECOND, None)
    c = _counters()
    assert c["train.program_cache.miss.step"] == 1 and c["train.program_cache.hit.step"] == 1
    assert counted.n == 1


def test_cache_keeps_the_most_recently_used():
    cache = train_loop._ProgramCache(2)
    built = []

    def get(key):
        return cache.get("step", key, lambda: built.append(key) or key)

    for key in ("a", "b", "a", "c", "a", "b"):
        get(key)
    assert built == ["a", "b", "c", "b"]  # "b" went when "c" came


def test_cache_under_threads_returns_each_key_its_own_program(counted):
    """More threads than cores churn a cache smaller than their keys: every
    lookup gets the program built for its own key, and each is counted."""
    cache = train_loop._ProgramCache(2)
    workers, rounds, keys = (os.cpu_count() or 1) + 4, 50, ("a", "b", "c")
    wrong = []
    barrier = threading.Barrier(workers)

    def worker(i):
        barrier.wait()
        for r in range(rounds):
            key = keys[(i + r) % len(keys)]
            fn = cache.get("step", key, lambda: (key, object()))
            if fn[0] != key:
                wrong.append((key, fn[0]))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(i,)) for i in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert wrong == []
    c = _counters()
    assert c.get("train.program_cache.hit.step", 0) + c["train.program_cache.miss.step"] == workers * rounds
