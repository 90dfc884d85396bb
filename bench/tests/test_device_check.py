"""The device check and the peaks table: every result names its device, and
a device the table does not know is an error, never a default."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402
from bench import harness  # noqa: E402


def test_cpu_is_no_accelerator():
    with pytest.raises(harness.NoAccelerator):
        harness.check_device(1)


def test_peaks_table_knows_v5e_and_refuses_others():
    peaks = harness.load_peaks("TPU v5 lite")
    assert peaks["bf16_flops"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        harness.load_peaks("TPU v9 imaginary")


def test_every_manifest_entry_has_its_files():
    manifest = json.load(open(os.path.join(tiny.ROOT, "BENCHMARK.json")))
    bench = os.path.join(tiny.ROOT, "bench")
    for w in manifest["workloads"]:
        wl = json.load(open(os.path.join(bench, "workloads", f"{w['name']}.json")))
        assert wl["config"] == w["config"] and wl["chips"] == w["chips"]
        assert os.path.exists(os.path.join(bench, "drivers", f"{wl['driver']}.py"))
    for m in manifest["per_layer"]:
        assert os.path.exists(harness.reader_path(m["name"]))
    for c in manifest["configs"]:
        assert json.load(open(os.path.join(tiny.ROOT, c["file"])))["source"]


def test_sub_seeds_take_any_whole_number():
    big = 2**31 + 12345
    assert harness.sub_seed(big, "data") == harness.sub_seed(big, "data")
    assert harness.sub_seed(big, "data") != harness.sub_seed(big + 1, "data")
    assert 0 <= harness.sub_seed(2**70, "x") < 2**31
