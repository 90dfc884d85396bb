"""Driver: a tuning study whose trials train a language model on chip slices.

The paper's section 5.2 shape.  ``TPESampler`` proposes each trial's
optimizer settings (``suggest_train_config``: learning rate, warm-up, weight
decay), ``SuccessiveHalvingPruner`` looks at every report, and each trial
trains the configuration's model through the program's ``Trainer`` on one
slice, driven by ``TrialSliceScheduler.run``: one slice per chip of the
cell, each running one trial at a time.  All workers are closed loop: a
slice asks for its next trial only when the previous one has ended.

Traffic (the workload file's ``traffic``):

- ``trial_steps`` / ``report_every``: a trial's length and its reports;
- ``max_trials``: the scheduler's budget (more than a window can start);
- ``open_at``: ``"start"`` opens the window as the scheduler starts, so
  trial set-up and every trial's compilation are inside it;
  ``"first_report"`` opens it at the first report, so they are not;
- ``sampler_seed``: TPE's seed.  It is fixed so that every run draws the same
  sequence of trial settings and does the same work; ``--seed`` draws the
  weights and the training data.

The window closes at the first report (a loss read that waits for the
device) after ``--seconds``; the trial reporting stops there, and trials
asked later are told PRUNED untrained, outside the window.  Tokens count
every step that a report inside the window confirmed.

What ``correct`` compares, after the window:

- the first ``check.steps`` steps of ``check.trials`` trials drawn from the
  seed, as the window's own step calls produced them, against
  ``reference/smollm.py``: each step's loss, the first gradient as AdamW
  received it (read off its first moment), and each leaf's change;
- every prune decision against ``reference/sha.py``, given the values
  reported before it;
- the stored state of every trial: its reports, final state and value.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import threading
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import repro.core as hpo
from repro.core.frozen import TrialState
from repro.launch.mesh import make_auto_mesh
from repro.models import BlockDef, ModelConfig
from repro.train import Trainer, TrainConfig, train_loop
from repro.tune import LMTuneSpec, TrialSliceScheduler
from repro.tune.objective import suggest_train_config

from bench import flops
from bench.harness import Outcome, memory_peak_bytes
from bench.reference import sha, smollm


def model_config(c: dict) -> ModelConfig:
    """The program's model configuration for the configuration file ``c``."""
    return ModelConfig(
        name=c["name"],
        d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim", 0),
        d_ff=c["intermediate_size"],
        vocab=c["vocab_size"],
        n_layers=c["num_hidden_layers"],
        superblock=(BlockDef(kind="attn"),),
        n_superblocks=c["num_hidden_layers"],
        tie_embeddings=c["tie_word_embeddings"],
        rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        param_dtype=c["param_dtype"],
        compute_dtype=c["compute_dtype"],
    )


class Tokens:
    """The training data: for each step, ``[batch, seq + 1]`` token ids drawn
    from a Zipf law (exponent ``zipf``) over a seeded permutation of the
    vocabulary, so a model can learn something in a few steps.  A step's
    batch depends only on (seed, step); all rows differ."""

    def __init__(self, seed: int, batch: int, seq: int, vocab: int, zipf: float, annotate=None):
        self.seed, self.batch, self.seq = seed, batch, seq
        self.perm = np.random.default_rng(seed).permutation(vocab).astype(np.int32)
        w = 1.0 / np.arange(1, vocab + 1) ** zipf
        self.cdf = np.cumsum(w) / np.sum(w)
        self.annotate = annotate
        self._step = 0

    def batch_at(self, step: int) -> dict:
        u = np.random.default_rng([self.seed, step]).random((self.batch, self.seq + 1))
        ids = self.perm[np.minimum(np.searchsorted(self.cdf, u), len(self.perm) - 1)]
        return {"tokens": np.ascontiguousarray(ids[:, :-1]), "labels": np.ascontiguousarray(ids[:, 1:])}

    def skip_to(self, step: int) -> None:
        self._step = step

    def next_batch(self) -> dict:
        with self.annotate("bench.data"):
            b = self.batch_at(self._step)
        self._step += 1
        return b


# -- reading a trial's first steps off the window's own step calls ------------------------


@jax.jit
def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


@jax.jit
def _leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in jax.tree.leaves(tree)]


@jax.jit
def _change_norms(new, old):
    return [
        jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old))
    ]


class TrialRecord:
    def __init__(self, number: int, tcfg, steps: int):
        self.number, self.tcfg, self.steps = number, tcfg, steps
        self.calls = 0
        self.losses: list = []  # device scalars of the first ``steps`` steps
        self.moment_norms = None  # per leaf, AdamW's first moment after step 1
        self.change_norms = None  # per leaf, params after ``steps`` steps minus before
        self.paths = None
        self.reports: list = []  # (seq, step, value, pruned)
        self.counted = 0  # steps already counted into the window's tokens
        self.stopped = False  # stopped by the window's close
        self.p0 = None
        self.step_fn = None
        self.avals = None


class StepProbe:
    """Wraps the step that the trainer jits for each trial (the program's
    ``train_loop._jit_on_mesh``), and reads the trial's first steps off its
    outputs: losses, the first moment after step 1, and each parameter's
    change after ``steps`` steps.  It adds small programs of its own after
    those steps and changes nothing the step computes."""

    def __init__(self, annotate):
        self.annotate = annotate
        self.local = threading.local()
        self._orig = train_loop._jit_on_mesh
        train_loop._jit_on_mesh = self._jit

    def close(self) -> None:
        train_loop._jit_on_mesh = self._orig

    def _jit(self, *args, **kwargs):
        fn = self._orig(*args, **kwargs)
        rec = getattr(self.local, "rec", None)
        if rec is None:
            return fn
        rec.step_fn = fn
        return partial(self._call, fn, rec)

    def _call(self, fn, rec, params, opt_state, step_no, batch):
        n = rec.calls
        rec.calls += 1
        if n == 0:
            rec.avals = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=getattr(x, "sharding", None)),
                (params, opt_state, step_no, batch),
            )
            rec.paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]]
            rec.p0 = _copy(params)
        with self.annotate("bench.step_compile" if n == 0 else "bench.step_dispatch"):
            out = fn(params, opt_state, step_no, batch)
        if n < rec.steps:
            new_params, new_opt, metrics = out
            rec.losses.append(metrics["loss"])
            if n == 0:
                rec.moment_norms = _leaf_norms(new_opt["m"])
            if n == rec.steps - 1:
                rec.change_norms = _change_norms(new_params, rec.p0)
                rec.p0 = None
        return out


def step_program_peak(rec: TrialRecord) -> int:
    """Bytes the step program needs on its device while it runs, from the
    compiler's ``memory_analysis`` (the allocator's peak counter misses the
    step's temporaries)."""
    ma = rec.step_fn.lower(*rec.avals).compile().memory_analysis()
    return int(
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        - ma.alias_size_in_bytes + ma.temp_size_in_bytes
    )


# -- the study ----------------------------------------------------------------------------


class Window:
    """The window's bookkeeping, shared by the slices' threads."""

    def __init__(self, run, open_at: str):
        self.run = run
        self.open_at = open_at
        self.lock = threading.Lock()
        self.opened = self.closed = False
        self.steps = 0
        self.seq = 0
        self.report_s: list = []
        self.trials_started = 0

    def open(self) -> None:
        self.run.open_window(SETTINGS_FREE)
        self.opened = True

    def on_report(self, rec: TrialRecord, step: int, value: float, pruned: bool, dt: float) -> bool:
        """Book one report; returns whether the trial stops here."""
        with self.lock:
            self.seq += 1
            rec.reports.append((self.seq, step, value, pruned))
            if self.closed:
                rec.stopped = True
                return True
            if not self.opened:
                if self.open_at == "first_report":
                    self.open()
                    rec.counted = step
                return False
            self.report_s.append(dt)
            self.steps += step - rec.counted
            rec.counted = step
            if time.perf_counter() >= self.run.deadline:
                self.run.close_window()
                self.closed = True
                rec.stopped = True
                return True
            return False


class Objective:
    """What each trial runs: train under the trial's settings on its slice."""

    def __init__(self, run, model, spec, data_seed, zipf, window, probe, check_steps):
        self.run, self.model, self.spec = run, model, spec
        self.data_seed, self.zipf = data_seed, zipf
        self.window, self.probe = window, probe
        self.check_steps = check_steps
        self.records: dict = {}
        self.untrained: set = set()

    def tokens(self) -> Tokens:
        return Tokens(self.data_seed, self.spec.batch, self.spec.seq, self.model.vocab,
                      self.zipf, self.run.annotate)

    def __call__(self, trial, mesh) -> float:
        with self.window.lock:
            late = self.window.closed
            if not late and self.window.opened:
                self.window.trials_started += 1
        if late:
            self.untrained.add(trial.number)
            raise hpo.TrialPruned("asked after the window closed")
        tcfg = suggest_train_config(trial, self.spec)
        tcfg = dataclasses.replace(tcfg, seed=self.run.sub_seed(f"weights:{trial.number}"))
        rec = TrialRecord(trial.number, tcfg, self.check_steps)
        self.records[trial.number] = rec

        def report(step: int, loss: float) -> bool:
            t0 = time.perf_counter()
            with self.run.annotate("bench.report"):
                trial.report(loss, step)
                pruned = trial.should_prune()
            return self.window.on_report(rec, step, loss, pruned, time.perf_counter() - t0) or pruned

        self.probe.local.rec = rec
        try:
            with self.run.annotate("bench.trial_setup"):
                result = Trainer(self.model, tcfg, self.tokens(), mesh=mesh, report_fn=report).run()
        finally:
            self.probe.local.rec = None
            rec.p0 = None  # a trial stopped before its last followed step
        if result.get("pruned"):
            raise hpo.TrialPruned(f"stopped at step {result['step']}")
        return result["last_loss"]


#: compile-cache entries of programs that do not depend on a trial's
#: settings: the window's cache keeps them from run to run
SETTINGS_FREE = ("jit_init-",)


def warm_up(run, model, spec, mesh, probe, data, compile_step: bool) -> TrialRecord:
    """One short training in set-up: compiles or loads what every trial
    reuses (the initialiser, data transfer, the probe's programs) and gives
    the step program's memory footprint.  With ``compile_step`` its step is
    compiled for real, not loaded from a cache, as a study that has already
    compiled trials has done, so that the window's per-trial compiles are
    those of a running study and not the process's first."""
    if compile_step:
        run.use_window_cache(SETTINGS_FREE)
    tcfg = spec_tcfg(run)
    rec = TrialRecord(-1, tcfg, probe_steps(run))
    probe.local.rec = rec
    try:
        Trainer(model, tcfg, data, mesh=mesh).run()
    finally:
        probe.local.rec = None
    return rec


def spec_tcfg(run) -> TrainConfig:
    """The warm-up's settings (a trial's are drawn by the sampler)."""
    return TrainConfig(lr=1e-3, warmup_steps=1, total_steps=probe_steps(run),
                       weight_decay=0.01, eval_every=probe_steps(run), checkpoint_every=10**9,
                       seed=run.sub_seed("warm-up"))


def probe_steps(run) -> int:
    return int(run.cell.workload["check"]["steps"])


# -- the checks ---------------------------------------------------------------------------


def gap_by_leaf(got: dict, ref: dict) -> float:
    """The worst leaf's gap between two per-leaf norms, relative to the
    reference's norm of that leaf or of the median leaf, whichever is
    larger.  Leaves whose reference norm is under a thousandth of the
    median leaf's are left out: they move by round-off alone."""
    med = float(np.median(list(ref.values())))
    return max(abs(got[k] - r) / max(r, med) for k, r in ref.items() if r >= 1e-3 * med)


def program_steps(rec: TrialRecord) -> dict:
    """What the window's step calls produced for ``rec``'s first steps: each
    loss, the per-leaf norm of the first gradient as AdamW received it (its
    first moment after one step over ``1 - b1``), and of each leaf's change."""
    scale = 1.0 / (1.0 - rec.tcfg.b1)
    return {
        "losses": [float(x) for x in rec.losses],
        "grad": {p: float(x) * scale for p, x in zip(rec.paths, rec.moment_norms)},
        "change": {p: float(x) for p, x in zip(rec.paths, rec.change_norms)},
    }


def reference_steps(cfg: dict, rec: TrialRecord, data: Tokens, **kwargs) -> dict:
    """The reference's first steps for ``rec``'s trial: the same weights
    (from its seed), settings and batches."""
    t = rec.tcfg
    batches = [data.batch_at(i) for i in range(rec.steps)]
    hyper = {"lr": t.lr, "warmup": t.warmup_steps, "weight_decay": t.weight_decay,
             "total_steps": t.total_steps, "b1": t.b1, "b2": t.b2, "eps": cfg["adam_eps"],
             "clip_norm": t.clip_norm}
    return smollm.first_steps(
        cfg, t.seed, hyper,
        np.stack([b["tokens"] for b in batches]), np.stack([b["labels"] for b in batches]),
        **kwargs,
    )


def gaps(got: dict, ref: dict) -> dict:
    """The numbers that can be compared (the workload's ``limits`` name the
    ones that are): the largest relative gap of a step's loss, and the worst
    leaf's gap of the first gradient (as AdamW received it) and of the
    change."""
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(got["losses"], ref["losses"])),
        "grad_gap": gap_by_leaf(got["grad"], ref["grad"]),
        "update_gap": gap_by_leaf(got["change"], ref["change"]),
    }


def prune_mismatches(records: dict, failed: set, r: int, eta: int) -> int:
    """Prune decisions that differ from successive halving's, each judged on
    the values the other trials had reported at that step before it."""
    events = sorted(
        (seq, num, step, value, pruned)
        for num, rec in records.items() if num not in failed
        for seq, step, value, pruned in rec.reports
    )
    seen: dict = {}
    bad = 0
    for _, num, step, value, pruned in events:
        peers = [v for n, v in seen.get(step, []) if n != num]
        if sha.prunes(value, step, peers, r, eta) != pruned:
            bad += 1
        seen.setdefault(step, []).append((num, value))
    return bad


def stored_mismatches(study, records: dict, untrained: set) -> int:
    """Trials whose stored reports, state or value differ from what the
    window saw: a trained trial keeps every report, and ends COMPLETE with its
    last loss or PRUNED with its last report; a trial asked after the close
    is PRUNED with no report."""
    bad = 0
    for t in study.get_trials(deepcopy=False):
        if t.number in untrained:
            ok = t.state == TrialState.PRUNED and not t.intermediate_values
        elif t.number in records:
            rec = records[t.number]
            want = {step: value for _, step, value, _ in rec.reports}
            last = rec.reports[-1][2] if rec.reports else None
            ok = (
                t.intermediate_values == want
                and t.state in (TrialState.COMPLETE, TrialState.PRUNED)
                and t.value == last
                and (t.state == TrialState.PRUNED) == (rec.reports[-1][3] or rec.stopped)
            )
        else:
            ok = t.state == TrialState.FAIL
        bad += not ok
    return bad


# -- the run ------------------------------------------------------------------------------


def run(run) -> Outcome:
    w, cfg = run.cell.workload, run.cell.config
    traffic, check, limits = w["traffic"], w["check"], w["limits"]
    model = model_config(cfg)
    batch, seq = cfg["batch"], cfg["seq"]
    spec = LMTuneSpec(vocab=model.vocab, seq=seq, batch=batch,
                      total_steps=traffic["trial_steps"], eval_every=traffic["report_every"])
    meshes = [make_auto_mesh((1, 1), ("data", "model"), devices=[d]) for d in run.devices]
    data_seed = run.sub_seed("data")
    probe = StepProbe(run.annotate)
    window = Window(run, traffic["open_at"])
    objective = Objective(run, model, spec, data_seed, traffic["zipf"], window, probe,
                          probe_steps(run))
    try:
        warm = warm_up(run, model, spec, meshes[0], probe, objective.tokens(),
                       compile_step=traffic["open_at"] == "start")
        program_peak = step_program_peak(warm)
        run.use_checkout_cache()
        study = hpo.create_study(
            sampler=hpo.TPESampler(seed=traffic["sampler_seed"]),
            pruner=hpo.SuccessiveHalvingPruner(
                min_resource=cfg["pruner"]["min_resource"],
                reduction_factor=cfg["pruner"]["reduction_factor"],
            ),
        )
        scheduler = TrialSliceScheduler(study, meshes, objective)
        if traffic["open_at"] == "start":
            window.open()
        scheduler.run(traffic["max_trials"])
        if not window.closed:  # the budget ran out first
            run.close_window()
            window.closed = True
    finally:
        probe.close()
    window_s = run.window_s
    peak = memory_peak_bytes(run.devices, program_peak)

    failed = {t.number for t in study.trials if t.state == TrialState.FAIL}
    records = objective.records
    started = [n for n in records if n not in failed]
    rng = np.random.default_rng(run.sub_seed("check"))
    followed = [n for n in sorted(records) if records[n].change_norms is not None]
    picks = rng.choice(followed, size=min(check["trials"], len(followed)), replace=False)
    # free the program's state before the reference runs on the chip
    del scheduler
    gc.collect()
    run.use_checkout_cache()
    followed_steps = []
    for n in picks:
        rec = records[int(n)]
        got, ref = program_steps(rec), reference_steps(cfg, rec, objective.tokens())
        followed_steps.append((got, ref, gaps(got, ref)))
    checks = [
        (k, max((g[k] for _, _, g in followed_steps), default=math.inf), lim)
        for k, lim in limits.items()
    ] + [
        ("prune_mismatch", prune_mismatches(records, failed, cfg["pruner"]["min_resource"],
                                            cfg["pruner"]["reduction_factor"]), 0),
        ("stored_mismatch", stored_mismatches(study, records, objective.untrained), 0),
        ("failed_trials", len(failed), 0),
    ]
    tokens = window.steps * batch * seq
    rate = tokens / window_s / len(meshes)
    # one rate, named per cell kind: with trial set-up in the window
    # (short trials) or not (one long trial)
    e2e = {"trial_tokens_per_chip_s": rate, "train_tokens_per_chip_s": rate, "setup_s": run.setup_s}
    readings = {
        "window_s": window_s,
        "tokens": tokens,
        "steps": window.steps,
        "trials_started": window.trials_started,
        "compile_s": run.compile_log.seconds.get("window", 0.0),
        "report_s": sum(window.report_s),
        "reports": len(window.report_s),
        "flops_per_token": flops.decoder_train_flops_per_token(cfg, seq),
        "chips": len(meshes),
        "step_module": "jit_" + getattr(warm.step_fn, "__name__", "step"),
    }
    notes = {
        "trials": len(records),
        "followed": [int(n) for n in picks],
        "losses": [got["losses"] for got, _, _ in followed_steps],
        "ref_losses": [ref["losses"] for _, ref, _ in followed_steps],
        "program_peak_bytes": program_peak,
    }
    return Outcome(e2e, len(started), len(failed), checks, readings, peak, notes)
