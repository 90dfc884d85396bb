#!/usr/bin/env python3
"""The readings each correctness limit is set from, on the chip.

    python bench/controls.py --cell <cell> --seeds 11 12 13 ...

For every seed, on the cell's own sizes and through the same path its runs
take, it prints one JSON line with the compared numbers of:

- ``program``: the system under test, as a run of the cell would read it;
- ``control``: the reference computed in the precision below the one the
  configuration states, put in the program's place (float8 products for a
  bfloat16 model; bfloat16 for the float32 Parzen scorer);
- ``half_batch`` (training cells): the reference with half of each batch
  left out, the loss taken over the rest;
- ``stale_fit`` and ``lowest_score`` (live-ask): an ask fitted without the
  newest trial, and one that suggests its worst candidate.

A step that returns its state unchanged reads ``update_gap`` 1 by
definition and needs no run.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)
os.environ.setdefault("TPU_LOG_DIR", os.path.join(_ROOT, ".bench_out", "tpu_logs"))

import contextlib  # noqa: E402

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402


def smollm_readings(cell: "harness.Cell", seeds: list, device, controls: int = 10**9):
    """Yields one dict of readings per seed for a training cell; the first
    ``controls`` seeds also read the control and the half batch."""
    import jax.numpy as jnp

    import repro.core as hpo
    from repro.launch.mesh import make_auto_mesh
    from repro.train import Trainer
    from repro.tune import LMTuneSpec
    from repro.tune.objective import suggest_train_config

    drv = harness.load_module(os.path.join(harness.BENCH, "drivers", "hpo_trials.py"))
    cfg, traffic = cell.config, cell.workload["traffic"]
    steps = int(cell.workload["check"]["steps"])
    model = drv.model_config(cfg)
    spec = LMTuneSpec(vocab=model.vocab, seq=cfg["seq"], batch=cfg["batch"],
                      total_steps=traffic["trial_steps"], eval_every=traffic["report_every"])
    mesh = make_auto_mesh((1, 1), ("data", "model"), devices=[device])
    # the settings of the cell's first trial, which its runs follow
    study = hpo.create_study(sampler=hpo.TPESampler(seed=traffic["sampler_seed"]))
    first = suggest_train_config(study.ask(), spec)
    probe = drv.StepProbe(lambda name: contextlib.nullcontext())
    try:
        for k, seed in enumerate(seeds):
            tcfg = dataclasses.replace(first, seed=harness.sub_seed(seed, "weights:0"))
            data = drv.Tokens(harness.sub_seed(seed, "data"), cfg["batch"], cfg["seq"],
                              model.vocab, traffic["zipf"], lambda name: contextlib.nullcontext())
            rec = drv.TrialRecord(0, tcfg, steps)
            probe.local.rec = rec
            Trainer(model, tcfg, data, mesh=mesh,
                    report_fn=lambda step, loss: step >= steps).run()
            probe.local.rec = None
            rec.p0 = None
            ref = drv.reference_steps(cfg, rec, data)
            out = {"seed": seed, "program": drv.gaps(drv.program_steps(rec), ref)}
            if k < controls:
                low = drv.reference_steps(cfg, rec, data, round_to=jnp.float8_e4m3fn)
                half = drv.reference_steps(cfg, rec, data, half_batch=True)
                out["control"] = drv.gaps(low, ref)
                out["half_batch"] = drv.gaps(half, ref)
            yield out
    finally:
        probe.close()


def parzen_readings(cell: "harness.Cell", seeds: list, asks: int, controls: int = 10**9):
    """Yields one dict of readings per seed for the live-ask cell, on every
    kernel call of ``asks`` asks: the program's, the control's (the
    reference scorer in bfloat16 in the kernel's place, the value suggested
    by its best score), and the faults ``stale_fit`` (the reference fit
    from the history without its newest trial) and ``lowest_score`` (the
    candidate the reference scores lowest suggested)."""
    import ml_dtypes

    import repro.core as hpo

    from bench.reference import parzen

    drv = harness.load_module(os.path.join(harness.BENCH, "drivers", "live_ask.py"))
    cfg, traffic = cell.config, cell.workload["traffic"]
    dims, (low, high) = cfg["dims"], cfg["bounds"]
    for k, seed in enumerate(seeds):
        storage = hpo.InMemoryStorage()
        seeder = hpo.create_study(study_name="history", storage=storage,
                                  sampler=hpo.RandomSampler(seed=harness.sub_seed(seed, "history")))
        drv.seed_history(seeder, cfg["history"], traffic["history_batch"], dims, low, high)
        study = hpo.load_study("history", storage,
                               sampler=hpo.TPESampler(seed=harness.sub_seed(seed, "tpe")))
        with drv.KernelSample(asks * dims, 0) as sample:
            for _ in range(asks):
                t = study.ask()
                study.tell(t, parzen.rastrigin(drv.suggest_all(t, dims, low, high, sample)))
        history = drv.History(study.get_trials(deepcopy=False), dims)
        out = {"seed": seed, "calls": len(sample.kept),
               "program": drv.compare(sample.kept, history, low, high)}
        if k < controls:
            ctrl = {"score_err": 0.0, "choice_gap": 0.0}
            stale, lowest = 0.0, 0.0
            for (number, i), args, _ in sample.kept:
                cands = args[0]
                below, above = history.fit(number, i, low, high)
                ref_scores = parzen.score(cands, *below, *above)
                lp = parzen.score(*args, round_to=ml_dtypes.bfloat16)
                ctrl["score_err"] = max(ctrl["score_err"], parzen.score_error(lp, parzen.score(*args)))
                ctrl["choice_gap"] = max(ctrl["choice_gap"],
                                         drv.choice_gap(cands, ref_scores, cands[int(np.argmax(lp))]))
                old = history.fit(number, i, low, high, drop_last=1)
                stale = max(stale, drv.fit_error((cands, *old[0], *old[1]), below, above, low, high))
                lowest = max(lowest, drv.choice_gap(cands, ref_scores, cands[int(np.argmin(ref_scores))]))
            out["control"] = ctrl
            out["stale_fit"] = {"fit_err": stale}
            out["lowest_score"] = {"choice_gap": lowest}
        yield out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--asks", type=int, default=50)
    ap.add_argument("--control-seeds", type=int, default=10**9,
                    help="read the control and the faults on the first this many seeds only")
    args = ap.parse_args(argv)
    manifest = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell = harness.Cell(manifest, args.cell)
    import jax

    device = jax.devices()[0]
    if device.platform == "cpu":
        print("controls: no accelerator", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", harness.CACHE_DIR)
    if cell.workload["driver"] == "hpo_trials":
        rows = smollm_readings(cell, args.seeds, device, args.control_seeds)
    else:
        rows = parzen_readings(cell, args.seeds, args.asks, args.control_seeds)
    for row in rows:
        print(json.dumps(dict(row, cell=args.cell)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
