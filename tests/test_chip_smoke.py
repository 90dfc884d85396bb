"""``chip_smoke.py``'s phases at smoke size on the CPU.

The script's ``main()`` needs a TPU; these tests import its phases and run
them with the Pallas kernels in interpret mode and a small model, so a
wrong path, argument or check fails here before it costs chip time.  The
four-chip phase runs on virtual devices in ``tests/test_parallel.py``.
"""

import os
import sys

import pytest

jax = pytest.importorskip("jax")

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro import configs  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402


def test_device_phase_names_the_platform_it_found():
    with pytest.raises(chip_smoke.SmokeFailure, match="'cpu'"):
        chip_smoke.phase_device("tpu")
    assert chip_smoke.phase_device("cpu")["platform"] == "cpu"


def test_sampler_phase_runs_both_kernels(monkeypatch):
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")  # interpret-mode kernels on CPU
    # small histories must still reach the device engine
    monkeypatch.setattr(kops, "TPE_JIT_THRESHOLD", 1000)
    jax.clear_caches()  # fresh traces, so the trace counts see this phase
    out = chip_smoke.phase_sampler(0, n_trials=300, mo_trials=40)
    assert out["engine_fallbacks"] == 0
    assert out["parzen_candidate_sizes"] == [24, kops.SCORE_TABLE_SIZE]
    assert out["mc_hv_calls"] > 0
    assert out["traces"]["pallas.parzen"] > 0 and out["traces"]["pallas.mc_hv"] > 0
    assert out["parzen_max_abs_err"] < chip_smoke.PARZEN_ATOL


def test_sampler_phase_fails_when_a_kernel_never_runs():
    # without REPRO_USE_PALLAS the CPU takes the jitted jnp scorer, so the
    # Pallas kernels are never called and the phase must say so
    with pytest.raises(chip_smoke.SmokeFailure, match="never ran"):
        chip_smoke.phase_sampler(0, n_trials=60, mo_trials=20)


def test_trials_phase_trains_through_the_scheduler():
    cfg = configs.get_smoke_config("smollm-135m")
    out = chip_smoke.phase_trials(0, cfg, "cpu", batch=2, seq=32, steps=4)
    assert len(out["states"]) == 3
    assert set(out["states"]) <= {"COMPLETE", "PRUNED"}
    for rec in out["trials"].values():
        assert rec["devices"] == [str(jax.devices()[0])]
        assert abs(rec["losses"][0] - 5.545) < chip_smoke.INIT_LOSS_TOL  # ln(256)
