"""Define-by-run HPO over the LM model zoo with ASHA pruning — the paper's
technique as a first-class feature of the training framework.

Each trial dynamically constructs an architecture (dense / mLSTM / mamba2 /
MoE family, depth, width, expert count...) and an optimizer config, trains it
with repro.train on synthetic data, reports eval losses to the ASHA pruner,
and stops early if outranked (paper Alg. 1, no repechage).

    PYTHONPATH=src python examples/tune_lm.py --trials 12
"""

import argparse
import sys

sys.path.insert(0, "src")

import repro.core as hpo
from repro.launch.compile_cache import enable_compile_cache
from repro.tune import LMTuneSpec, make_lm_objective


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=10)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--storage", default=None, help="e.g. sqlite:///tune.db for distributed")
    ap.add_argument("--study", default="tune-lm")
    args = ap.parse_args()

    enable_compile_cache()
    spec = LMTuneSpec(total_steps=args.steps, eval_every=max(args.steps // 8, 1))
    study = hpo.create_study(
        study_name=args.study,
        storage=args.storage,
        sampler=hpo.TPESampler(seed=0, n_startup_trials=4),
        pruner=hpo.SuccessiveHalvingPruner(min_resource=1, reduction_factor=3),
        load_if_exists=True,
    )
    study.optimize(make_lm_objective(spec), n_trials=args.trials, catch=(Exception,))

    states = [t.state.name for t in study.trials]
    print(f"\ntrials: {len(states)}  complete: {states.count('COMPLETE')} "
          f"pruned: {states.count('PRUNED')}  failed: {states.count('FAIL')}")
    best = study.best_trial
    print(f"best loss {best.values[0]:.4f} with {best.params}")
    hpo.save_dashboard(study, "/tmp/tune_lm_dashboard.html")
    print("dashboard: /tmp/tune_lm_dashboard.html")


if __name__ == "__main__":
    main()
