"""Each driver end to end at tiny sizes on the CPU, in a temporary copy of
the benchmark to which the tiny cells are added as new files and entries."""

import hashlib
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402

LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny.make_checkout(str(tmp_path_factory.mktemp("checkout")))


@pytest.fixture
def cpu(monkeypatch, checkout):
    tiny.use_checkout(monkeypatch, checkout)
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")  # the Parzen kernel, interpreted
    return checkout


@pytest.mark.parametrize("cell", sorted(tiny.TINY_CELLS))
def test_cell_runs_and_is_correct(cpu, cell):
    code, line, err = tiny.run_cell(cell, trace=0, seconds=4.0)
    assert code == 0, err
    assert list(line) == LINE_KEYS
    assert line["correct"] is True, err
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    manifest = json.load(open(os.path.join(cpu, "BENCHMARK.json")))
    want = {m["name"] for m in manifest["end_to_end"] if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert err.rstrip().splitlines()[-1].startswith("check ")


def test_traced_run_reads_per_layer_metrics(cpu):
    code, line, err = tiny.run_cell("tiny-tpe.live-ask", trace=1, seconds=2.0)
    assert code == 0, err
    assert list(line) == LINE_KEYS[:5] + ["breakdown", "checks"]
    assert {"ask_host_ms", "score_roundtrip_ms"} <= set(line["metrics"])
    assert "busy_s" in line["device"] and line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_added_workload_and_metric_need_no_edit(cpu):
    """A new cell and a new per-layer metric are files and manifest entries:
    no file the benchmark had is touched."""
    bench = os.path.join(cpu, "bench")

    def digests():
        out = {}
        for d, _, files in os.walk(bench):
            for f in files:
                if "__pycache__" not in d:
                    p = os.path.join(d, f)
                    out[os.path.relpath(p, bench)] = hashlib.sha256(open(p, "rb").read()).hexdigest()
        return out

    before = digests()
    with open(os.path.join(bench, "metrics", "history_end.py"), "w") as f:
        f.write("def read(r):\n    return float(r.host['asks'])\n")
    wl = dict(tiny.TINY_CELLS["tiny-tpe.live-ask"][1], traffic=dict(
        tiny.TINY_CELLS["tiny-tpe.live-ask"][1]["traffic"], warm_asks=1))
    with open(os.path.join(bench, "workloads", "tiny-tpe.one-warm-ask.json"), "w") as f:
        json.dump(wl, f)
    path = os.path.join(cpu, "BENCHMARK.json")
    manifest = json.load(open(path))
    manifest["workloads"].append({"name": "tiny-tpe.one-warm-ask", "config": "tiny-tpe",
                                  "traffic": "one-warm-ask", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "tiny-tpe.live-ask" in m.get("workloads", ()):
            m["workloads"].append("tiny-tpe.one-warm-ask")
    manifest["per_layer"].append({"name": "history_end", "unit": "asks", "better": "higher",
                                  "source": "host_clock", "layer": "study API and TPE fit",
                                  "moves": "ask_p50_ms", "workloads": ["tiny-tpe.one-warm-ask"]})
    json.dump(manifest, open(path, "w"))
    try:
        code, line, err = tiny.run_cell("tiny-tpe.one-warm-ask", trace=1, seconds=1.0)
        assert code == 0, err
        assert line["metrics"]["history_end"]["value"] == line["attempted"]
        after = digests()
        assert {k: v for k, v in after.items() if k in before} == before
    finally:
        os.remove(os.path.join(bench, "metrics", "history_end.py"))
        os.remove(os.path.join(bench, "workloads", "tiny-tpe.one-warm-ask.json"))
        manifest["workloads"].pop()
        manifest["per_layer"].pop()
        for m in manifest["end_to_end"] + manifest["per_layer"]:
            if "tiny-tpe.one-warm-ask" in m.get("workloads", ()):
                m["workloads"].remove("tiny-tpe.one-warm-ask")
        json.dump(manifest, open(path, "w"))


def test_command_exits_nonzero_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "smollm-135m-sha.short-trials",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=tiny.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr
