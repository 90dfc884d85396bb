"""Train-step construction + the host-side training loop.

``make_train_step`` builds the pure step function lowered by both the real
trainer and the dry-run: grad of the chunked-CE loss, optional microbatch
accumulation (scan), optimizer update, donation-friendly signature.

``Trainer`` adds the production concerns: sharded init and a step pinned to
the trainer's mesh (a trial on mesh slice k lives on slice k's devices),
checkpoint/restart (auto-resume from the latest step), deterministic data
skip on resume, eval hooks that feed the HPO pruner, and graceful preemption
(SIGTERM -> final checkpoint).

The trainer's programs, the step and the sharded initialiser, are built once
per process for each key that decides them (model config, optimizer key,
microbatch, mesh, rules, state and batch shardings) and kept in a small LRU
cache: a trial's settings reach them as operands in the optimizer state, so
trials of equal shape share both programs and only the first compiles.  The
counters ``train.program_cache.{hit,miss}.{step,init}`` count lookups.

``Trainer.run`` records its phases as telemetry spans (on the profiler's
host timeline when telemetry is enabled): ``train.init``, then per step
``train.batch``, ``train.compile`` (the first step call, which compiles only
on a cache miss) or ``train.dispatch``, and at each eval ``train.loss_sync``
and ``train.report``.  Run by the trial scheduler, they carry the trial's id.
"""

from __future__ import annotations

import dataclasses
import signal
import threading
from collections import OrderedDict
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import telemetry
from repro.models import (
    ModelConfig,
    abstract_params,
    init_model_params,
    loss_fn,
    params_logical,
)
from repro.models.sharding import (
    TRAIN_RULES,
    ShardingRules,
    logical_to_sharding,
    tree_shardings,
    wrap_with_sharding_ctx,
)

from .checkpoint import CheckpointManager
from .optimizer import Optimizer, make_optimizer

__all__ = [
    "TrainConfig", "make_train_step", "Trainer", "make_sharded_init", "batch_shardings",
]


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    clip_norm: float = 1.0
    microbatch: int = 0  # 0 = no accumulation; else per-step slices
    checkpoint_every: int = 200
    eval_every: int = 20
    seed: int = 0


def make_optimizer_for(cfg: ModelConfig, tcfg: TrainConfig) -> Optimizer:
    sched = dict(lr=tcfg.lr, warmup_steps=tcfg.warmup_steps, total_steps=tcfg.total_steps,
                 clip_norm=tcfg.clip_norm)
    if cfg.optimizer == "adamw":
        return make_optimizer(
            "adamw", **sched, b1=tcfg.b1, b2=tcfg.b2, weight_decay=tcfg.weight_decay,
        )
    if cfg.optimizer == "adafactor":
        return make_optimizer("adafactor", **sched)
    return make_optimizer("sgd", **sched)


#: programs kept per process, step and initialiser alike; the least recently
#: used goes first, so a study that draws many architectures keeps its newest
_PROGRAMS_KEPT = 16


class _ProgramCache:
    """The trainer's jitted programs by key; the scheduler's slices look
    them up from their own threads."""

    def __init__(self, size: int):
        self.size = size
        self._fns: OrderedDict = OrderedDict()
        self._lock = threading.Lock()

    def get(self, kind: str, key, build: Callable):
        """The ``kind`` program ("step" or "init") for ``key``, built by
        ``build()`` on a miss.  Slices that miss on the same key at once each
        build (outside the lock, so a slow build holds up no other key) and
        all get the first one kept."""
        with self._lock:
            fn = self._fns.get((kind, key))
            if fn is not None:
                self._fns.move_to_end((kind, key))
        hit = fn is not None
        if not hit:
            built = build()
            with self._lock:
                fn = self._fns.setdefault((kind, key), built)
                self._fns.move_to_end((kind, key))
                if len(self._fns) > self.size:
                    self._fns.popitem(last=False)
        telemetry.inc(f"train.program_cache.{'hit' if hit else 'miss'}.{kind}")
        return fn

    def clear(self) -> None:
        with self._lock:
            self._fns.clear()


_programs = _ProgramCache(_PROGRAMS_KEPT)


def _tree_key(tree) -> tuple:
    leaves, treedef = jax.tree.flatten(tree)
    return tuple(leaves), treedef


def _rules_key(rules: ShardingRules) -> tuple:
    return tuple(sorted(rules.rules.items()))


def _moments(opt_state: dict) -> dict:
    """The optimizer state without its settings: what a checkpoint keeps."""
    return {k: v for k, v in opt_state.items() if k != "hyper"}


def make_train_step(cfg: ModelConfig, opt: Optimizer, microbatch: int = 0) -> Callable:
    """Returns step(params, opt_state, step_no, batch) -> (params, opt_state, metrics).

    The step's ``program_key`` names what decides its program; the
    optimizer's settings are not part of it, since they ride in
    ``opt_state``."""

    def grads_of(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, batch), has_aux=True
        )(params)
        return loss, metrics, grads

    def train_step(params, opt_state, step_no, batch):
        if microbatch and microbatch > 1:
            # grad accumulation: scan over microbatch slices of the batch dim
            def resh(x):
                b = x.shape[0]
                return x.reshape(microbatch, b // microbatch, *x.shape[1:])

            mb = jax.tree.map(resh, batch)

            def body(acc, mbatch):
                loss, metrics, grads = grads_of(params, mbatch)
                acc = jax.tree.map(jnp.add, acc, (loss, grads))
                return acc, None

            zero = (
                jnp.float32(0.0),
                jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            )
            (loss_sum, grad_sum), _ = jax.lax.scan(body, zero, mb)
            loss = loss_sum / microbatch
            grads = jax.tree.map(lambda g: g / microbatch, grad_sum)
            metrics = {}
        else:
            loss, metrics, grads = grads_of(params, batch)
        new_params, new_opt, opt_metrics = opt.update(grads, opt_state, params, step_no)
        out_metrics = {"loss": loss, **metrics, **opt_metrics}
        return new_params, new_opt, out_metrics

    train_step.program_key = (cfg, opt.key, microbatch)
    return train_step


def make_sharded_init(cfg: ModelConfig, opt: Optimizer, mesh, rules: ShardingRules):
    """jit-compiled ``init(key, hyper)`` with output shardings pinned to the
    rules table — parameters are born sharded, never materialized on one
    host — and the optimizer's settings ``hyper`` placed in its state.  One
    per process for each config, optimizer key, mesh and rules."""

    def build():
        aps = abstract_params(cfg)
        logical = params_logical(cfg)
        p_sh = tree_shardings(aps, logical, mesh, rules)
        opt_abs = jax.eval_shape(opt.init, aps)
        o_sh = _opt_shardings(opt_abs, p_sh)

        def init(key, hyper):
            params = init_model_params(cfg, key)
            return params, opt.init(params, hyper)

        return jax.jit(init, out_shardings=(p_sh, o_sh)), p_sh, o_sh

    return _programs.get("init", (cfg, opt.key, mesh, _rules_key(rules)), build)


def batch_shardings(batch: dict, mesh, rules: ShardingRules) -> dict:
    """Shardings for a batch dict (arrays or ShapeDtypeStructs): the batch
    dim over the rules' batch axes, sequence over "seq"."""

    def one(name, s):
        if name == "image_embeds":
            logical = ("batch", None, None)
        elif len(s.shape) == 3:  # audio [B, K, S]
            logical = ("batch", None, "seq")
        else:
            logical = ("batch", "seq")
        return logical_to_sharding(logical, s.shape, mesh, rules)

    return {k: one(k, v) for k, v in batch.items()}


def _opt_shardings(opt_abs, param_shardings):
    """Optimizer state shardings: inherit from the matching parameter where
    shapes coincide (adam m/v); adafactor's factored vr/vc inherit the param
    spec minus the reduced axis (so expert/vocab shards stay sharded);
    scalars (the settings) are replicated."""
    from jax.sharding import NamedSharding, PartitionSpec

    flat_p = {
        tuple(str(k) for k in path): s
        for path, s in jax.tree_util.tree_leaves_with_path(param_shardings)
    }

    def param_spec_for(keys):
        for start in range(len(keys)):
            if keys[start:] in flat_p:
                return flat_p[keys[start:]]
        return None

    some = next(iter(flat_p.values()))
    replicated = NamedSharding(some.mesh, PartitionSpec())

    def one(path, leaf):
        if not leaf.shape:
            return replicated
        keys = tuple(str(k) for k in path)
        hit = param_spec_for(keys)
        if hit is not None:
            return hit
        if keys and keys[-1] in ("vr", "vc"):
            hit = param_spec_for(keys[:-1])
            if hit is not None:
                spec = list(hit.spec)
                spec += [None] * (len(leaf.shape) + 1 - len(spec))
                drop = -1 if keys[-1] == "vr" else -2
                del spec[drop]
                # drop axes that no longer divide
                clean = []
                for dim, ax in zip(leaf.shape, spec):
                    axes = (ax,) if isinstance(ax, str) else (ax or ())
                    size = 1
                    for a in axes:
                        size *= hit.mesh.shape[a]
                    clean.append(ax if size and dim % max(size, 1) == 0 else None)
                return NamedSharding(hit.mesh, PartitionSpec(*clean))
        return replicated

    leaves = jax.tree_util.tree_leaves_with_path(opt_abs)
    vals = [one(p, l) for p, l in leaves]
    return jax.tree.unflatten(jax.tree.structure(opt_abs), vals)


def _jit_on_mesh(train_step, mesh, rules: ShardingRules, state_sh, b_sh):
    """``train_step`` jitted with its state and batch pinned to ``mesh`` (on
    the default device where ``mesh`` is None).  One per process for each
    step key and placement: later calls with the same return the same jitted
    function, which compiles once per batch shape."""
    from jax.sharding import NamedSharding, PartitionSpec

    def build():
        if mesh is None:
            return jax.jit(train_step, donate_argnums=(0, 1))
        p_sh, o_sh = state_sh
        scalar_sh = NamedSharding(mesh, PartitionSpec())
        return jax.jit(
            wrap_with_sharding_ctx(train_step, mesh, rules),
            in_shardings=(p_sh, o_sh, scalar_sh, b_sh),
            out_shardings=(p_sh, o_sh, scalar_sh),
            donate_argnums=(0, 1),
        )

    key = (train_step.program_key, mesh, _rules_key(rules), _tree_key(state_sh), _tree_key(b_sh))
    return _programs.get("step", key, build)


class Trainer:
    """Host-side loop with checkpoint/restart and pruner hooks.

    With a ``mesh``, parameters and optimizer state are born on that mesh
    (``make_sharded_init`` under ``rules``, default ``TRAIN_RULES``) and the
    step is jitted with the matching in/out shardings, so every array of the
    run stays on the mesh's devices.  Without one, arrays live on the
    default device."""

    def __init__(
        self,
        cfg: ModelConfig,
        tcfg: TrainConfig,
        data_iter,
        workdir: str | None = None,
        mesh=None,
        rules: ShardingRules | None = None,
        report_fn: Callable[[int, float], bool] | None = None,
    ):
        self.cfg = cfg
        self.tcfg = tcfg
        self.data = data_iter
        self.workdir = workdir
        self.mesh = mesh
        self.rules = rules
        self.report_fn = report_fn  # returns True if the trial should stop (pruned)
        self.opt = make_optimizer_for(cfg, tcfg)
        self.ckpt = CheckpointManager(workdir) if workdir else None
        self._preempted = False

    def _install_sigterm(self):
        def handler(signum, frame):
            self._preempted = True

        try:
            signal.signal(signal.SIGTERM, handler)
        except ValueError:
            pass  # not the main thread (e.g. HPO worker threads)

    def run(self) -> dict:
        self._install_sigterm()
        cfg, tcfg = self.cfg, self.tcfg
        mesh, rules = self.mesh, self.rules or TRAIN_RULES
        with telemetry.span("train.init"):
            key = jax.random.PRNGKey(tcfg.seed)
            train_step = make_train_step(cfg, self.opt, tcfg.microbatch)
            start_step = 0
            state_sh = b_sh = None
            if mesh is None:
                params = init_model_params(cfg, key)
                opt_state = self.opt.init(params)
            else:
                init, p_sh, o_sh = make_sharded_init(cfg, self.opt, mesh, rules)
                params, opt_state = init(key, self.opt.hyper)
                state_sh = (p_sh, o_sh)
            if self.ckpt is not None:
                # a checkpoint holds the params and the moments; the settings
                # are always this run's
                restored = self.ckpt.restore_latest(
                    (params, _moments(opt_state)),
                    None if state_sh is None else (state_sh[0], _moments(state_sh[1])),
                )
                if restored is not None:
                    start_step, (params, moments) = restored
                    opt_state = {**moments, "hyper": opt_state["hyper"]}

        self.data.skip_to(start_step)
        losses = []
        last = None
        step_fn = None  # from the first batch, whose shapes it needs
        for step in range(start_step, tcfg.total_steps):
            with telemetry.span("train.batch"):
                batch = self.data.next_batch()
                if step_fn is None:
                    if mesh is not None:
                        b_sh = batch_shardings(batch, mesh, rules)
                    step_fn = _jit_on_mesh(train_step, mesh, rules, state_sh, b_sh)
                if mesh is not None:
                    # host numpy straight onto this mesh's devices
                    batch = jax.device_put(batch, b_sh)
            # the first call traces, lowers and compiles the step on a cache miss
            with telemetry.span("train.compile" if step == start_step else "train.dispatch"):
                params, opt_state, metrics = step_fn(
                    params, opt_state, np.int32(step), batch
                )
            last = metrics
            if (step + 1) % tcfg.eval_every == 0 or step + 1 == tcfg.total_steps:
                with telemetry.span("train.loss_sync"):
                    loss = float(metrics["loss"])
                losses.append(loss)
                with telemetry.span("train.report"):
                    stop = self.report_fn is not None and self.report_fn(step + 1, loss)
                if stop:
                    # pruned by the HPO layer: stop immediately, do not checkpoint
                    # (the paper's no-repechage design: pruned trials never resume)
                    return {"pruned": True, "last_loss": loss, "step": step + 1,
                            "params": params}
            if self.ckpt is not None and (
                (step + 1) % tcfg.checkpoint_every == 0 or self._preempted
            ):
                self.ckpt.save(step + 1, (params, _moments(opt_state)))
                if self._preempted:
                    return {"preempted": True, "step": step + 1,
                            "last_loss": float(last["loss"]) if last else float("nan")}
        if self.ckpt is not None:
            self.ckpt.wait()
        return {
            "pruned": False,
            "last_loss": float(last["loss"]) if last is not None else float("nan"),
            "losses": losses,
            "step": tcfg.total_steps,
            "params": params,
        }
