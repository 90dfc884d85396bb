"""The controls at a size a test run can hold: the reference computed in the
precision below the configuration's, put in the program's place, fails the
cell's limits, while the program passes them."""

import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402
from bench import controls, harness  # noqa: E402


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny.make_checkout(str(tmp_path_factory.mktemp("checkout")))


@pytest.fixture
def cell(monkeypatch, checkout):
    tiny.use_checkout(monkeypatch, checkout)
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")

    def make(name):
        manifest = harness.load_json(os.path.join(checkout, "BENCHMARK.json"))
        return harness.Cell(manifest, name)

    return make


def _fails(reading: dict, limits: dict) -> bool:
    return any(reading[k] > limit for k, limit in limits.items())


def test_training_control_and_half_batch_fail_where_the_program_passes(cell):
    c = cell("tiny-lm.long-trial")
    limits = c.workload["limits"]
    rows = list(controls.smollm_readings(c, [101, 202, 303], jax.devices()[0]))
    for row in rows:
        assert not _fails(row["program"], limits), row
        assert _fails(row["control"], limits), row
        assert _fails(row["half_batch"], limits), row


def test_parzen_control_fails_where_the_kernel_passes(cell):
    c = cell("tiny-tpe.live-ask")
    limits = c.workload["limits"]
    for row in controls.parzen_readings(c, [101, 202, 303], asks=3):
        assert row["calls"] > 0
        assert not _fails(row["program"], limits), row
        assert _fails(row["control"], limits), row
        assert row["stale_fit"]["fit_err"] > limits["fit_err"], row
        assert row["lowest_score"]["choice_gap"] > limits["choice_gap"], row
