#!/usr/bin/env python3
"""Start the tuning system on one TPU chip and check what comes out.

    python chip_smoke.py [--seed N]          # one chip: phases 1-3
    python chip_smoke.py --four-chips        # four chips: the slice phase only

Every phase runs in this one process: a chip belongs to one process, and a
child would fail or hang on it.

1. device: the default JAX backend must be a TPU; anything else exits
   non-zero naming the platform found.
2. sampler engine: a study seeded with 2,000 random trials over 8 numeric
   parameters is loaded with the default ``TPESampler`` and asked one wave
   of 8 (the Pallas Parzen kernel, at the direct-ask shape and at the
   4096-point score-table shape); a 5-objective study of 500 trials is asked
   a wave under MOTPE (the Pallas MC-hypervolume kernel).  Every kernel call
   is counted, and the first of each input shape is compared with the numpy
   reference on the same inputs.
3. trials at full width: ``TrialSliceScheduler`` over one one-chip slice
   runs 3 TPE trials with successive halving; each trains the published
   ``smollm-135m`` on synthetic tokens at batch 8 x seq 2048 for 6 steps.

``--four-chips`` runs four such trials concurrently, one per chip, and the
same four trainings one after another on the first chip, and requires equal
losses.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; a failed phase
prints no such line and exits non-zero.  All data comes from ``--seed``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import repro.core as hpo  # noqa: E402
from repro import configs  # noqa: E402
from repro.core import telemetry  # noqa: E402
from repro.core.moo import _mc_counts_numpy  # noqa: E402
from repro.core.samplers.tpe import _score_numpy  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_auto_mesh, slice_mesh  # noqa: E402
from repro.train import SyntheticLM, Trainer  # noqa: E402
from repro.tune import LMTuneSpec, TrialSliceScheduler  # noqa: E402
from repro.tune.objective import suggest_train_config  # noqa: E402

#: kernel vs float64 numpy reference, Parzen scores in log space; MC-HV
#: counts are integers and must match exactly
PARZEN_RTOL, PARZEN_ATOL = 1e-4, 1e-3
#: the 4-chip losses must match the one-device rerun of the same trainings
LOSS_RTOL = 1e-5
#: first reported loss of a randomly initialised LM vs ln(vocab), in nats
INIT_LOSS_TOL = 0.5

#: sampler phase: trials asked per wave, numeric parameters and objectives
#: of the multi-objective study
WAVE, MO_PARAMS, MO_OBJECTIVES = 8, 8, 5
#: trials asked and told per round trip while the random history is seeded
SEED_BATCH = 500
#: trial phases: report (and let the pruner look) every this many steps;
#: trials on the one-chip slice; slices (one per chip) under --four-chips
REPORT_EVERY, N_TRIALS, N_SLICES = 2, 3, 4


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _emit(name: str, result: dict) -> None:
    print(f"[{name}] " + json.dumps(result, sort_keys=True, default=str), flush=True)


# -- phase 1: device -----------------------------------------------------------------


def phase_device(platform: str) -> dict:
    """The device JAX runs on; fails unless its backend is ``platform``."""
    found = jax.default_backend()
    _check(found == platform, f"JAX backend is {found!r}, not {platform!r}: no chip here")
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}


# -- phase 2: sampler engine -------------------------------------------------------


class _Recorder:
    """Wraps one kernel op of ``repro.kernels.ops`` while active: counts its
    calls and keeps the inputs and outputs of the first call of each input
    shape, for comparison with the numpy reference."""

    def __init__(self, name: str):
        self.name = name
        self.calls = 0
        self.kept: dict = {}

    def __enter__(self):
        self._orig = op = getattr(kops, self.name)

        def recorded(*args):
            out = op(*args)
            self.calls += 1
            key = tuple(np.shape(a) for a in args)
            if key not in self.kept:
                self.kept[key] = ([np.array(a) for a in args], out)
            return out

        setattr(kops, self.name, recorded)
        return self

    def __exit__(self, *exc):
        setattr(kops, self.name, self._orig)
        return False


def _so_objective(params: dict, shift: np.ndarray) -> float:
    x = np.array([params[f"x{i}"] for i in range(6)])
    return float(
        np.sum((x - shift) ** 2)
        + math.log10(params["lr"]) ** 2
        + (params["width"] - 12) ** 2 / 16.0
    )


def _suggest_so(trial) -> dict:
    params = {f"x{i}": trial.suggest_float(f"x{i}", -5.0, 5.0) for i in range(6)}
    params["lr"] = trial.suggest_float("lr", 1e-4, 1.0, log=True)
    params["width"] = trial.suggest_int("width", 1, 32)
    return params


def _dtlz2(x: np.ndarray, m: int) -> list:
    g = float(np.sum((x[m - 1:] - 0.5) ** 2))
    f = []
    for i in range(m):
        v = 1.0 + g
        for j in range(m - 1 - i):
            v *= math.cos(0.5 * math.pi * x[j])
        if i > 0:
            v *= math.sin(0.5 * math.pi * x[m - 1 - i])
        f.append(v)
    return f


def _mo_objective(trial) -> list:
    x = np.array([trial.suggest_float(f"x{i}", 0.0, 1.0) for i in range(MO_PARAMS)])
    return _dtlz2(x, MO_OBJECTIVES)


def _seed_history(study, n_trials: int, run) -> None:
    """``n_trials`` finished trials through batched ask and tell."""
    while n_trials > 0:
        wave = study.ask(min(SEED_BATCH, n_trials))
        study.tell_batch([(t, run(t)) for t in wave])
        n_trials -= len(wave)


def phase_sampler(seed: int, n_trials: int = 2000, mo_trials: int = 500) -> dict:
    """Seed, reload under TPE / MOTPE, ask one wave each; check the kernels."""
    rng = np.random.RandomState(seed)
    shift = rng.uniform(-3.0, 3.0, 6)
    was_enabled = telemetry.enabled()
    telemetry.enable()
    telemetry.reset()
    kops.reset_traces()
    t0 = time.perf_counter()
    try:
        storage = hpo.InMemoryStorage()
        so = hpo.create_study(
            study_name="smoke-so", storage=storage, sampler=hpo.RandomSampler(seed=seed)
        )
        _seed_history(so, n_trials, lambda t: _so_objective(_suggest_so(t), shift))
        mo = hpo.create_study(
            study_name="smoke-mo", storage=storage,
            sampler=hpo.RandomSampler(seed=seed + 1),
            directions=["minimize"] * MO_OBJECTIVES,
        )
        _seed_history(mo, mo_trials, _mo_objective)
        t_seed = time.perf_counter()

        with _Recorder("parzen_score_op") as parzen, _Recorder("mc_hv_counts_op") as mc_hv:
            so = hpo.load_study("smoke-so", storage, sampler=hpo.TPESampler(seed=seed))
            trials = so.ask(WAVE)
            so.tell_batch([(t, _so_objective(_suggest_so(t), shift)) for t in trials])
            t_so = time.perf_counter()
            mo = hpo.load_study(
                "smoke-mo", storage,
                sampler=hpo.TPESampler(seed=seed, multi_objective=True),
            )
            trials = mo.ask(WAVE)
            mo.tell_batch([(t, _mo_objective(t)) for t in trials])
            t_mo = time.perf_counter()
        fallbacks = telemetry.counter("sampler.engine_fallbacks").value
    finally:
        if not was_enabled:
            telemetry.disable()

    parzen_err = 0.0
    for args, out in parzen.kept.values():
        ref = _score_numpy(*args)
        got = np.asarray(out, dtype=np.float64)
        _check(
            np.allclose(got, ref, rtol=PARZEN_RTOL, atol=PARZEN_ATOL),
            f"Parzen kernel disagrees with numpy on C={len(args[0])}: "
            f"max |err| {np.max(np.abs(got - ref))}",
        )
        parzen_err = max(parzen_err, float(np.max(np.abs(got - ref))))
    for (pts, samples), (excl, total) in mc_hv.kept.values():
        excl_ref, total_ref = _mc_counts_numpy(
            pts.astype(np.float32), samples.astype(np.float32)
        )
        _check(
            np.array_equal(np.asarray(excl), excl_ref) and float(total) == total_ref,
            f"MC-HV kernel counts differ from numpy on {pts.shape[0]} points",
        )
    cand_sizes = sorted({shape[0][0] for shape in parzen.kept})
    result = {
        "parzen_calls": parzen.calls,
        "parzen_candidate_sizes": cand_sizes,
        "parzen_max_abs_err": parzen_err,
        "mc_hv_calls": mc_hv.calls,
        "mc_hv_point_counts": sorted({shape[0][0] for shape in mc_hv.kept}),
        "traces": {
            "pallas.parzen": kops.trace_count("pallas.parzen"),
            "pallas.mc_hv": kops.trace_count("pallas.mc_hv"),
        },
        "engine_fallbacks": fallbacks,
        "interpret": kops.should_interpret(),
        "seed_s": t_seed - t0,
        "so_wave_s": t_so - t_seed,
        "mo_wave_s": t_mo - t_so,
    }
    _check(fallbacks == 0, f"sampler.engine_fallbacks = {fallbacks}, want 0")
    _check(parzen.calls > 0 and mc_hv.calls > 0, f"a kernel never ran: {result}")
    _check(
        kops.SCORE_TABLE_SIZE in cand_sizes and min(cand_sizes) < kops.SCORE_TABLE_SIZE,
        f"Parzen kernel missed the direct-ask or score-table shape: {cand_sizes}",
    )
    _check(
        min(result["traces"].values()) > 0, f"kernel trace counts: {result['traces']}"
    )
    return result


# -- phase 3 / four chips: trials at full width ---------------------------------------


class _CompileClock:
    """Seconds XLA spends compiling, from JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if event == self.EVENT:
            with self._lock:
                self.seconds += duration
                self.count += 1


def _memory_stats(device) -> dict:
    """The device allocator's counters as JAX reports them (``peak_*`` and
    ``bytes_*``); empty where the backend keeps none."""
    return dict(sorted((device.memory_stats() or {}).items()))


class TrialRunner:
    """The objective every trial runs: train ``cfg`` under the trial's
    ``suggest_train_config`` on the trial's mesh, report every
    ``REPORT_EVERY`` steps, and record what each trial did."""

    def __init__(self, cfg, batch: int, seq: int, steps: int, seed: int):
        self.cfg = cfg
        self.seed = seed
        self.spec = LMTuneSpec(
            vocab=cfg.vocab, seq=seq, batch=batch, total_steps=steps, eval_every=REPORT_EVERY
        )
        self.records: dict = {}
        self._lock = threading.Lock()

    def train(self, tcfg, mesh, report_fn=None) -> dict:
        data = SyntheticLM(self.cfg, batch=self.spec.batch, seq=self.spec.seq, seed=self.seed)
        marks = [time.perf_counter()]
        losses = []

        def report(step: int, loss: float) -> bool:
            marks.append(time.perf_counter())
            losses.append(loss)
            return report_fn(step, loss) if report_fn else False

        result = Trainer(self.cfg, tcfg, data, mesh=mesh, report_fn=report).run()
        devices = {d for x in jax.tree.leaves(result.pop("params", ())) for d in x.devices()}
        gaps = np.diff(marks[1:]) / self.spec.eval_every
        return {
            "losses": losses,
            "devices": sorted(str(d) for d in devices),
            "platforms": sorted({d.platform for d in devices}),
            "first_report_s": marks[1] - marks[0] if len(marks) > 1 else None,
            "step_s": float(np.median(gaps)) if len(gaps) else None,
            "span": (marks[0], time.perf_counter()),
            "pruned": bool(result.get("pruned")),
        }

    def __call__(self, trial, mesh) -> float:
        tcfg = suggest_train_config(trial, self.spec)

        def report(step: int, loss: float) -> bool:
            trial.report(loss, step)
            return trial.should_prune()

        rec = self.train(tcfg, mesh, report)
        rec["tcfg"] = tcfg
        rec["mesh_devices"] = sorted(str(d) for d in mesh.devices.flat)
        with self._lock:
            self.records[trial.number] = rec
        if rec["pruned"]:
            raise hpo.TrialPruned(f"pruned after {len(rec['losses'])} reports")
        return rec["losses"][-1]


def _states(study) -> list:
    """Each trial's state, with the cause of a failure."""
    return [
        t.state.name + (f" ({t.system_attrs.get('fail:exception')})" if t.state.name == "FAIL" else "")
        for t in study.trials
    ]


def _check_trial(number: int, rec: dict, vocab: int, platform: str) -> None:
    losses = rec["losses"]
    _check(bool(losses) and all(map(math.isfinite, losses)), f"trial {number} losses {losses}")
    _check(
        abs(losses[0] - math.log(vocab)) < INIT_LOSS_TOL,
        f"trial {number} first loss {losses[0]} is not near ln({vocab}) = {math.log(vocab)}",
    )
    _check(
        rec["devices"] == rec["mesh_devices"] and rec["platforms"] == [platform],
        f"trial {number} arrays on {rec['devices']}, its slice is {rec['mesh_devices']}",
    )


def _trial_summary(rec: dict) -> dict:
    keep = ("losses", "devices", "first_report_s", "step_s", "pruned")
    return {k: rec[k] for k in keep}


def phase_trials(
    seed: int, cfg, platform: str, batch: int = 8, seq: int = 2048, steps: int = 6
) -> dict:
    """``N_TRIALS`` TPE + successive-halving trials on one one-chip slice."""
    clock = _CompileClock()
    device = jax.devices()[0]
    mesh = make_auto_mesh((1, 1), ("data", "model"), devices=[device])
    study = hpo.create_study(
        sampler=hpo.TPESampler(seed=seed),
        pruner=hpo.SuccessiveHalvingPruner(min_resource=REPORT_EVERY, reduction_factor=3),
    )
    runner = TrialRunner(cfg, batch, seq, steps, seed)
    t0 = time.perf_counter()
    TrialSliceScheduler(study, [mesh], runner).run(N_TRIALS)
    wall = time.perf_counter() - t0
    states = _states(study)
    _check(
        len(states) == N_TRIALS and not any(s.startswith("FAIL") for s in states),
        f"trial states {states}",
    )
    for number, rec in runner.records.items():
        _check_trial(number, rec, cfg.vocab, platform)
    return {
        "model": cfg.name,
        "states": states,
        "trials": {n: _trial_summary(r) for n, r in sorted(runner.records.items())},
        "compile_s": clock.seconds,
        "compiles": clock.count,
        "wall_s": wall,
        "memory_stats": _memory_stats(device),
    }


def phase_four_chips(
    seed: int, cfg, platform: str, batch: int = 8, seq: int = 2048, steps: int = 6
) -> dict:
    """One trial per chip, concurrently through ``TrialSliceScheduler``;
    then the same trainings one after another on the first device."""
    devices = jax.devices()
    _check(len(devices) == N_SLICES, f"{len(devices)} devices, want {N_SLICES}")
    slices = slice_mesh(
        make_auto_mesh((N_SLICES, 1), ("data", "model"), devices=devices), N_SLICES
    )
    study = hpo.create_study(sampler=hpo.TPESampler(seed=seed), pruner=hpo.NopPruner())
    runner = TrialRunner(cfg, batch, seq, steps, seed)
    t0 = time.perf_counter()
    TrialSliceScheduler(study, slices, runner).run(N_SLICES)
    wall = time.perf_counter() - t0
    states = _states(study)
    _check(states == ["COMPLETE"] * N_SLICES, f"trial states {states}")
    recs = runner.records
    for number, rec in recs.items():
        _check_trial(number, rec, cfg.vocab, platform)
    placed = sorted(rec["devices"][0] for rec in recs.values())
    _check(len(set(placed)) == N_SLICES, f"trials share devices: {placed}")
    latest_start = max(rec["span"][0] for rec in recs.values())
    earliest_end = min(rec["span"][1] for rec in recs.values())
    _check(latest_start < earliest_end, "the slices' trials did not overlap in time")

    first = make_auto_mesh((1, 1), ("data", "model"), devices=[devices[0]])
    max_diff = 0.0
    t1 = time.perf_counter()
    for number, rec in sorted(recs.items()):
        again = runner.train(rec["tcfg"], first)
        a, b = np.asarray(rec["losses"]), np.asarray(again["losses"])
        _check(
            a.shape == b.shape and np.allclose(a, b, rtol=LOSS_RTOL, atol=0.0),
            f"trial {number}: slice losses {a} vs one-device losses {b}",
        )
        max_diff = max(max_diff, float(np.max(np.abs(a - b))))
    return {
        "model": cfg.name,
        "trials": {n: _trial_summary(r) for n, r in sorted(recs.items())},
        "overlap_s": earliest_end - latest_start,
        "concurrent_wall_s": wall,
        "sequential_wall_s": time.perf_counter() - t1,
        "loss_max_abs_diff": max_diff,
    }


# -- entry point ----------------------------------------------------------------------


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the four-slice phase and its one-device comparison",
    )
    args = ap.parse_args(argv)

    phase = "device"
    try:
        device = phase_device("tpu")
        _emit(phase, device)
        _emit("compile_cache", {"dir": enable_compile_cache()})
        cfg = configs.get_config("smollm-135m")
        if args.four_chips:
            phase = "four_chips"
            _emit(phase, phase_four_chips(args.seed, cfg, device["platform"]))
        else:
            phase = "sampler"
            _emit(phase, phase_sampler(args.seed))
            phase = "trials"
            _emit(phase, phase_trials(args.seed, cfg, device["platform"]))
    except SmokeFailure as e:
        print(f"chip_smoke: phase {phase} failed: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
