"""Checkpoint save/restore incl. resharding restore and trainer auto-resume."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import init_model_params
from repro.train import (
    CheckpointManager,
    SyntheticLM,
    TrainConfig,
    Trainer,
    restore_pytree,
    save_pytree,
)
from repro.train.train_loop import make_optimizer_for


def test_roundtrip_pytree(tmp_path):
    tree = {
        "a": jnp.arange(12.0).reshape(3, 4),
        "nested": {"b": jnp.ones((2, 2), jnp.bfloat16), "c": jnp.int32(7)},
    }
    path = str(tmp_path / "ck.ckpt")
    save_pytree(path, tree, step=42)
    step, restored = restore_pytree(path, jax.eval_shape(lambda: tree))
    assert step == 42
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_manager_gc_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for step in (10, 20, 30):
        mgr.save(step, {"x": jnp.full((4,), float(step))}, blocking=True)
    assert mgr.all_steps() == [20, 30]
    step, tree = mgr.restore_latest({"x": jax.ShapeDtypeStruct((4,), jnp.float32)})
    assert step == 30
    assert float(tree["x"][0]) == 30.0


def test_async_save_then_wait(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"x": jnp.zeros((128, 128))})
    mgr.wait()
    assert mgr.all_steps() == [1]


def test_shape_mismatch_raises(tmp_path):
    path = str(tmp_path / "ck.ckpt")
    save_pytree(path, {"x": jnp.zeros((4,))}, 0)
    with pytest.raises(ValueError):
        restore_pytree(path, {"x": jax.ShapeDtypeStruct((5,), jnp.float32)})


def test_trainer_resume_continues_step_count(tmp_path):
    cfg = configs.get_smoke_config("smollm-135m")
    data = SyntheticLM(cfg, batch=2, seq=32, seed=0)
    t1 = Trainer(cfg, TrainConfig(total_steps=6, checkpoint_every=3, eval_every=2), data, workdir=str(tmp_path))
    t1.run()
    mgr = CheckpointManager(str(tmp_path))
    assert 6 in mgr.all_steps()
    # second trainer resumes from 6 and continues to 10
    t2 = Trainer(
        cfg, TrainConfig(total_steps=10, checkpoint_every=3, eval_every=2),
        SyntheticLM(cfg, batch=2, seq=32, seed=0), workdir=str(tmp_path),
    )
    res = t2.run()
    assert res["step"] == 10


@pytest.mark.parametrize("on_mesh", [False, True])
def test_trainer_resumes_from_a_checkpoint_without_settings(tmp_path, on_mesh):
    """A checkpoint holds the params and the optimizer's moments, not its
    settings (as every checkpoint written before the settings joined the
    optimizer state): a trainer resumes from one under its own settings,
    and what it writes holds none either."""
    from repro.launch.mesh import make_auto_mesh

    cfg = configs.get_smoke_config("smollm-135m")
    tcfg = TrainConfig(total_steps=4, checkpoint_every=2, eval_every=2)
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    moments = {k: v for k, v in make_optimizer_for(cfg, tcfg).init(params).items() if k != "hyper"}
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(2, (params, moments), blocking=True)
    mesh = make_auto_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1]) if on_mesh else None
    res = Trainer(cfg, tcfg, SyntheticLM(cfg, batch=2, seq=32, seed=0), mesh=mesh,
                  workdir=str(tmp_path)).run()
    assert res["step"] == 4 and len(res["losses"]) == 1 and np.isfinite(res["losses"][0])
    assert mgr.all_steps()[-1] == 4
    with open(mgr._path(4) + ".json") as f:
        keys = json.load(f)["keys"]
    assert keys and not any("hyper" in k for k in keys)


def test_restore_under_different_sharding_subprocess(tmp_path):
    """Write a checkpoint with 1 device, restore sharded onto a 4-device mesh
    (elastic restart onto a different topology)."""
    import subprocess
    import sys

    cfg = configs.get_smoke_config("tinyllama-1.1b")
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    path = str(tmp_path / "p.ckpt")
    save_pytree(path, params, step=5)

    script = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, {os.path.abspath('src')!r})
import jax, numpy as np
from repro import configs
from repro.models import abstract_params, params_logical
from repro.models.sharding import TRAIN_RULES, tree_shardings
from repro.train import restore_pytree

cfg = configs.get_smoke_config("tinyllama-1.1b")
from repro.launch.mesh import make_auto_mesh
mesh = make_auto_mesh((2, 2), ("data", "model"))
aps = abstract_params(cfg)
sh = tree_shardings(aps, params_logical(cfg), mesh, TRAIN_RULES)
step, params = restore_pytree({path!r}, aps, sh)
assert step == 5
leaf = jax.tree.leaves(params)[0]
assert len(leaf.sharding.device_set) >= 1
total = sum(float(np.sum(np.asarray(x, np.float64) != 0)) for x in jax.tree.leaves(params))
assert total > 0
print("RESHARD_OK")
"""
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, timeout=240
    )
    assert "RESHARD_OK" in out.stdout, out.stderr[-2000:]
