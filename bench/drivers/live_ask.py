"""Driver: one closed-loop client asking a TPE study over a long history.

The paper's section 5.1 shape.  The study's in-memory storage is filled in
set-up with ``history`` finished trials of ``RandomSampler`` over the
configuration's objective; then the default ``TPESampler`` serves one client
that asks, evaluates and tells, one trial at a time.  Every tell moves the
observation version, so each ask refits TPE and scores its candidates
directly: one device call per numeric parameter, never the score table.

An ask, as the client feels it, is ``Study.ask()`` and the ``suggest_float``
of every parameter (define-by-run sampling happens in those calls).

Set-up warms every Parzen kernel bucket the window can reach: real asks at
the history's own size, and direct kernel calls at the larger buckets
(``traffic.warm_buckets``).

What ``correct`` compares, after the window, on ``check.calls`` of the
window's Parzen kernel calls drawn from the seed (a reservoir sample, the
window's last call always in it); each call is one parameter of one ask:

- ``score_err``: the kernel's scores against ``reference/parzen.py`` on the
  same component arrays;
- ``fit_err``: the two mixtures the kernel was given against those
  ``reference/tpe.py`` fits from the stored history at that ask (every trial
  told before it), compared as log-densities at the call's candidates and
  on a grid over the bounds;
- ``choice_gap``: how far the value the ask suggested lies below the best
  candidate by the reference's scores of that fit.

And the stored state of every trial told in the window: COMPLETE, its value
the objective's value at its stored parameters, every parameter in bounds.
"""

from __future__ import annotations

import time

import numpy as np

import repro.core as hpo
from repro.core import telemetry
from repro.core.frozen import TrialState
from repro.kernels import ops as kops

from bench.harness import Outcome, memory_peak_bytes
from bench.reference import parzen, tpe

#: points of the grid over the bounds at which ``fit_err`` compares mixtures
FIT_GRID = 65


class KernelSample:
    """Wraps ``kops.parzen_score_op`` while entered: counts its calls by
    shape and keeps a seeded reservoir of ``k`` calls (the ``tag`` set by
    the caller, inputs by reference, output), plus the latest call."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.tag = None
        self.rng = np.random.default_rng(seed)
        self.calls = 0
        self.kept: list = []
        self.last = None
        self.shapes: dict = {}

    def __enter__(self):
        self._orig = op = kops.parzen_score_op

        def recorded(*args):
            out = op(*args)
            i = self.calls
            self.calls += 1
            shape = (len(args[0]), len(args[1]), len(args[4]))
            self.shapes[shape] = self.shapes.get(shape, 0) + 1
            self.last = item = (self.tag, args, out)
            if i < self.k:
                self.kept.append(item)
            else:
                j = int(self.rng.integers(0, i + 1))
                if j < self.k:
                    self.kept[j] = item
            return out

        kops.parzen_score_op = recorded
        return self

    def __exit__(self, *exc):
        kops.parzen_score_op = self._orig
        return False


def suggest_all(trial, dims: int, low: float, high: float, sample=None) -> np.ndarray:
    """Every parameter of ``trial``; with a ``sample``, each kernel call is
    tagged with its trial's number and parameter."""
    x = np.empty(dims)
    for i in range(dims):
        if sample is not None:
            sample.tag = (trial.number, i)
        x[i] = trial.suggest_float(f"x{i}", low, high)
    return x


def fit_error(args, ref_below, ref_above, low: float, high: float) -> float:
    """The largest gap between the log-densities of the mixtures a kernel
    call was given (``args``) and of the reference's, at the call's
    candidates and on a grid over the bounds, relative to ``1 + |ref|``."""
    cands, got_below, got_above = args[0], args[1:4], args[4:7]
    xs = np.concatenate([np.asarray(cands, np.float64), np.linspace(low, high, FIT_GRID)])
    err = 0.0
    for got, ref in ((got_below, ref_below), (got_above, ref_above)):
        err = max(err, parzen.score_error(parzen.mixture_log_pdf(xs, *got),
                                          parzen.mixture_log_pdf(xs, *ref)))
    return err


def choice_gap(cands, ref_scores, chosen: float) -> float:
    """How far the suggested value ``chosen`` scores below the best of the
    call's candidates by the reference's ``ref_scores``, relative to
    ``1 + |best|``; infinite where it is none of the candidates."""
    hit = np.flatnonzero(np.asarray(cands, np.float64) == chosen)
    if not len(hit):
        return float("inf")
    best = float(np.max(ref_scores))
    return (best - float(ref_scores[hit[0]])) / (1.0 + abs(best))


class History:
    """The stored trials, by number: each one's parameters and value, to
    rebuild the fit of any ask from the trials told before it."""

    def __init__(self, trials, dims: int):
        done = sorted((t for t in trials if t.state == TrialState.COMPLETE), key=lambda t: t.number)
        self.numbers = np.array([t.number for t in done])
        self.values = np.array([t.value for t in done], np.float64)
        self.x = np.array([[t.params[f"x{i}"] for i in range(dims)] for t in done], np.float64)
        self.params = {t.number: t.params for t in done}

    def fit(self, number: int, i: int, low: float, high: float, drop_last: int = 0) -> tuple:
        """The reference fit of parameter ``i`` at the ask of trial
        ``number``; ``drop_last`` leaves out the newest trials (a stale fit)."""
        n = int(np.searchsorted(self.numbers, number)) - drop_last
        return tpe.fit(self.x[:n, i], self.values[:n], low, high)


def compare(sample_items, history: History, low: float, high: float) -> dict:
    """``score_err``, ``fit_err`` and ``choice_gap`` over the kept calls."""
    out = {"score_err": 0.0, "fit_err": 0.0, "choice_gap": 0.0}
    if not sample_items:
        return {k: float("inf") for k in out}
    for (number, i), args, got in sample_items:
        below, above = history.fit(number, i, low, high)
        ref_scores = parzen.score(args[0], *below, *above)
        chosen = history.params.get(number, {}).get(f"x{i}", float("nan"))
        out["score_err"] = max(out["score_err"], parzen.score_error(np.asarray(got), parzen.score(*args)))
        out["fit_err"] = max(out["fit_err"], fit_error(args, below, above, low, high))
        out["choice_gap"] = max(out["choice_gap"], choice_gap(args[0], ref_scores, chosen))
    return out


def seed_history(study, n: int, batch: int, dims: int, low: float, high: float) -> None:
    """``n`` finished random trials, asked and told ``batch`` at a time."""
    while n > 0:
        wave = study.ask(min(batch, n))
        study.tell_batch([(t, parzen.rastrigin(suggest_all(t, dims, low, high))) for t in wave])
        n -= len(wave)


def warm_buckets(buckets: list, n_cands: int, below: int) -> None:
    """One Parzen kernel call at each above-side bucket the window can
    reach, with the shapes the sampler passes (pow2-padded components)."""
    rng = np.random.default_rng(0)
    for k in buckets:
        lo = [rng.uniform(-1, 1, below), np.ones(below), np.zeros(below)]
        hi = [rng.uniform(-1, 1, k), np.ones(k), np.zeros(k)]
        np.asarray(kops.parzen_score_op(rng.uniform(-1, 1, n_cands), *lo, *hi))


def run(run) -> Outcome:
    w, cfg = run.cell.workload, run.cell.config
    traffic, check, limits = w["traffic"], w["check"], w["limits"]
    dims, (low, high) = cfg["dims"], cfg["bounds"]
    storage = hpo.InMemoryStorage()
    seeder = hpo.create_study(study_name="history", storage=storage,
                              sampler=hpo.RandomSampler(seed=run.sub_seed("history")))
    seed_history(seeder, cfg["history"], traffic["history_batch"], dims, low, high)
    study = hpo.load_study("history", storage, sampler=hpo.TPESampler(seed=run.sub_seed("tpe")))
    for _ in range(traffic["warm_asks"]):
        t = study.ask()
        study.tell(t, parzen.rastrigin(suggest_all(t, dims, low, high)))
    warm_buckets(traffic["warm_buckets"], traffic["n_ei_candidates"], traffic["below_bucket"])
    sample = KernelSample(check["calls"], run.sub_seed("check"))
    asks, values = [], {}
    with sample:
        run.open_window()
        deadline = run.deadline
        while True:
            a0 = time.perf_counter()
            if a0 >= deadline:
                break
            with run.annotate("bench.ask"):
                trial = study.ask()
                x = suggest_all(trial, dims, low, high, sample)
            a1 = time.perf_counter()
            with run.annotate("bench.objective"):
                value = parzen.rastrigin(x)
            with run.annotate("bench.tell"):
                study.tell(trial, value)
            asks.append(a1 - a0)
            values[trial.number] = value
        run.close_window()
    window_s = run.window_s
    peak = memory_peak_bytes(run.devices)

    kept = list(sample.kept)
    if sample.last is not None and all(sample.last is not item for item in kept):
        kept.append(sample.last)
    trials = study.get_trials(deepcopy=False)
    gaps = compare(kept, History(trials, dims), low, high)

    told = {t.number: t for t in trials if t.number in values}
    stored_bad = 0
    for number, value in values.items():
        t = told.get(number)
        x = np.array([t.params.get(f"x{i}", np.nan) for i in range(dims)]) if t else None
        ok = (
            t is not None
            and t.state == TrialState.COMPLETE
            and len(t.params) == dims
            and bool(np.all((x >= low) & (x <= high)))
            and t.value == value == parzen.rastrigin(x)
        )
        stored_bad += not ok

    lat = np.asarray(asks)
    e2e = {
        "ask_p50_ms": 1e3 * float(np.percentile(lat, 50)),
        "ask_p95_ms": 1e3 * float(np.percentile(lat, 95)),
        "trials_per_s": len(values) / window_s,
        "setup_s": run.setup_s,
    }
    spans = getattr(run, "telemetry", {}) or {}
    score = spans.get("histograms", {}).get("tpe.score", {})
    readings = {
        "asks": len(asks),
        "ask_s": float(np.sum(lat)),
        "score_s": float(score.get("sum", 0.0)),
        "score_calls": int(score.get("count", 0)),
        "kernel_calls": sample.calls,
    }
    checks = [(k, gaps[k], limits[k]) for k in ("score_err", "fit_err", "choice_gap")]
    checks.append(("stored_mismatch", stored_bad, 0))
    notes = {
        "asks": len(asks),
        "history_end": cfg["history"] + traffic["warm_asks"] + len(values),
        "kernel_calls": sample.calls,
        "kernel_shapes": {f"{c}x{kl}x{kg}": n for (c, kl, kg), n in sorted(sample.shapes.items())},
        "compared_calls": len(kept),
        "fallbacks": telemetry.counter("sampler.engine_fallbacks").value,
    }
    return Outcome(e2e, len(asks), 0, checks, readings, peak, notes)
