"""A temporary copy of the benchmark with tiny cells, run in this process on
the CPU: the tests steer the harness's device check and peaks table here,
so the benchmark itself keeps no CPU path."""

from __future__ import annotations

import io
import json
import os
import shutil
import sys
from contextlib import redirect_stderr, redirect_stdout

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

TINY_LM = {
    "name": "tiny-lm",
    "source": "a smoke-size decoder of the smollm-135m-sha layout",
    "hidden_size": 48, "intermediate_size": 96, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 256,
    "max_position_embeddings": 32, "rope_theta": 10000.0, "rms_norm_eps": 1e-06,
    "hidden_act": "silu", "tie_word_embeddings": True,
    "param_dtype": "float32", "compute_dtype": "bfloat16",
    "batch": 2, "seq": 32, "adam_eps": 1e-08,
    "pruner": {"name": "SuccessiveHalvingPruner", "min_resource": 2, "reduction_factor": 3},
    "reduced": [], "assumed": {},
}
TINY_TPE = {
    "name": "tiny-tpe", "source": "a small history of the tpe-rastrigin8d-h5000 shape",
    "objective": "rastrigin", "dims": 8, "bounds": [-5.12, 5.12], "history": 800,
    "reduced": [], "assumed": {},
}
#: cell -> (configuration, workload file)
TINY_CELLS = {
    "tiny-lm.short-trials": ("tiny-lm", {
        "config": "tiny-lm", "driver": "hpo_trials", "chips": 1, "why": "test",
        "traffic": {"trial_steps": 6, "report_every": 2, "max_trials": 8,
                    "open_at": "start", "sampler_seed": 0, "zipf": 1.1},
        "check": {"trials": 1, "steps": 2},
        "limits": {"loss_gap": 0.002, "grad_gap": 0.02, "update_gap": 0.01},
    }),
    "tiny-lm.long-trial": ("tiny-lm", {
        "config": "tiny-lm", "driver": "hpo_trials", "chips": 1, "why": "test",
        "traffic": {"trial_steps": 1000, "report_every": 5, "max_trials": 1,
                    "open_at": "first_report", "sampler_seed": 0, "zipf": 1.1},
        "check": {"trials": 1, "steps": 3},
        "limits": {"loss_gap": 0.002, "grad_gap": 0.02, "update_gap": 0.01},
    }),
    "tiny-tpe.live-ask": ("tiny-tpe", {
        "config": "tiny-tpe", "driver": "live_ask", "chips": 1, "why": "test",
        "traffic": {"history_batch": 100, "warm_asks": 3, "warm_buckets": [1024, 2048],
                    "n_ei_candidates": 24, "below_bucket": 32},
        "check": {"calls": 8},
        "limits": {"score_err": 1e-4, "fit_err": 1e-4, "choice_gap": 0.002},
    }),
}
PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def make_checkout(dest: str) -> str:
    """A copy of ``BENCHMARK.json`` and ``bench/`` with the tiny cells added
    as new files and new manifest entries."""
    os.makedirs(dest, exist_ok=True)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(dest, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for cfg in (TINY_LM, TINY_TPE):
        path = f"bench/configs/{cfg['name']}.json"
        with open(os.path.join(dest, path), "w") as f:
            json.dump(cfg, f)
        manifest["configs"].append({"name": cfg["name"], "source": cfg["source"], "file": path,
                                    "reduced": [], "why": "test"})
    for name, (cfg, wl) in TINY_CELLS.items():
        with open(os.path.join(dest, "bench", "workloads", f"{name}.json"), "w") as f:
            json.dump(wl, f)
        manifest["workloads"].append({"name": name, "config": cfg, "traffic": name.split(".", 1)[1],
                                      "chips": 1, "why": "test"})
        for m in manifest["end_to_end"] + manifest["per_layer"]:
            like = name.replace("tiny-lm", "smollm-135m-sha").replace("tiny-tpe", "tpe-rastrigin8d-h5000")
            if like in m.get("workloads", ()):
                m["workloads"].append(name)
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=2)
    return dest


def use_checkout(monkeypatch, dest: str) -> None:
    """Point the harness at ``dest`` and steer its device check to the CPU."""
    monkeypatch.setattr(harness, "ROOT", dest)
    monkeypatch.setattr(harness, "BENCH", os.path.join(dest, "bench"))
    out = os.path.join(dest, ".bench_out")
    monkeypatch.setattr(harness, "OUT_DIR", out)
    monkeypatch.setattr(harness, "CACHE_DIR", os.path.join(dest, ".jax_cache"))
    monkeypatch.setattr(harness, "WINDOW_CACHE_DIR", os.path.join(out, "window_cache"))
    monkeypatch.setattr(harness, "TRACE_DIR", os.path.join(out, "trace"))
    monkeypatch.setattr(
        harness, "check_device", lambda chips: {"platform": "cpu", "kind": "cpu", "count": 1}
    )
    monkeypatch.setattr(harness, "load_peaks", lambda kind: PEAKS)
    # the CPU's persistent cache entries only slow these runs down
    monkeypatch.setattr(harness, "_set_cache_dir", lambda path: None)


def run_cell(name: str, seed: int = 12345, seconds: float = 2.0, trace: int = 0) -> tuple:
    """``(exit code, result line, standard error)`` of one run, in-process."""
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        code = harness.main(["--workload", name, "--seed", str(seed),
                             "--seconds", str(seconds), "--trace", str(trace)])
    lines = out.getvalue().strip().splitlines()
    return code, json.loads(lines[-1]) if lines else None, err.getvalue()
