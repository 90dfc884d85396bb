"""The reduction from a trace to device numbers, and the yardstick's own
counts of operations."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402
from bench import flops  # noqa: E402
from bench.trace import TraceView, merge  # noqa: E402

MS = 1_000_000  # ns


def _view():
    """Device 0 runs ops at [1, 3), [2, 4) and [6, 7) ms of a [0, 10) ms
    window; device 1 runs [0, 5) ms.  The host asks in [0, 5) ms and tells in
    [5, 8) ms, with a report nested in the tell at [6, 8) ms."""
    ops = {
        0: [("fusion.1", 1 * MS, 3 * MS), ("parzen_score_kernel", 2 * MS, 4 * MS),
            ("fusion.1", 6 * MS, 7 * MS), ("outside", 11 * MS, 12 * MS)],
        1: [("fusion.2", 0, 5 * MS)],
    }
    modules = {0: [("jit_wrapped(7)", 1 * MS, 4 * MS), ("jit_other", 6 * MS, 7 * MS)]}
    spans = [("bench.ask", 0, 5 * MS), ("bench.tell", 5 * MS, 8 * MS),
             ("bench.report", 6 * MS, 8 * MS)]
    return TraceView(ops, modules, spans, (0, 10 * MS), [0, 1])


def test_merge_is_the_union():
    assert merge([(5, 6), (1, 3), (2, 4), (4, 5)]) == [(1, 6)]
    assert merge([(1, 2), (3, 4)]) == [(1, 2), (3, 4)]


def test_busy_union_averages_over_devices():
    v = _view()
    assert v.busy_intervals(0) == [(1 * MS, 4 * MS), (6 * MS, 7 * MS)]
    assert v.busy_s() == pytest.approx((4e-3 + 5e-3) / 2)
    assert v.window_s == pytest.approx(10e-3)


def test_time_per_name_counts_only_the_window():
    v = _view()
    assert v.op_seconds(lambda n: n == "fusion.1") == (2, pytest.approx(3e-3))
    assert v.op_seconds(lambda n: "parzen" in n) == (1, pytest.approx(2e-3))
    assert v.op_seconds(lambda n: n == "outside") == (0, 0)
    assert v.module_runs(lambda n: n.startswith("jit_wrapped")) == [pytest.approx(3e-3)]
    assert v.top_ops(2)[0] == ["?/fusion.2", pytest.approx(5e-3)]  # no program around it
    assert v.top_ops(3)[1] == ["jit_wrapped/fusion.1", pytest.approx(2e-3)]


def test_gaps_are_named_by_the_innermost_host_span():
    v = _view()
    assert v.gaps(0) == [(0, 1 * MS), (4 * MS, 6 * MS), (7 * MS, 10 * MS)]
    assert v.host_activity(int(0.5 * MS)) == "ask"
    assert v.host_activity(int(6.5 * MS)) == "report"
    assert v.host_activity(int(5.5 * MS)) == "tell"
    assert v.host_activity(int(9 * MS)) == "other"
    gaps = dict((k, v_) for k, v_ in v.idle_gaps(10))
    # device 0: 1 ms ask, 2 ms at 5 ms (tell), 3 ms at 8.5 ms (other);
    # device 1: 5 ms at 7.5 ms (report); averaged over the two
    assert gaps == {"ask": pytest.approx(0.5e-3), "tell": pytest.approx(1e-3),
                    "other": pytest.approx(1.5e-3), "report": pytest.approx(2.5e-3)}


def test_smollm_flops_match_a_hand_count():
    """From configs/smollm_135m.py: d 576, 9 heads of 64, 3 kv heads, d_ff
    1536, 30 layers, vocab 49152 (tied head)."""
    from repro import configs
    from repro.models import count_params

    cfg = json.load(open(os.path.join(tiny.ROOT, "bench", "configs", "smollm-135m-sha.json")))
    per_layer = 576 * 576 * 2 + 576 * 192 * 2 + 3 * 576 * 1536
    assert per_layer == 3_538_944
    matmul = 30 * per_layer + 576 * 49152
    assert flops.decoder_matmul_params(cfg) == matmul == 134_479_872
    # the program's own count adds the 61 norm gains of 576
    assert count_params(configs.get_config("smollm-135m")) == matmul + 61 * 576
    attention = 30 * 4 * 9 * 64 * 2049 / 2  # q.k and p.v over the causal half
    assert flops.decoder_train_flops_per_token(cfg, 2048) == 3 * (2 * matmul + attention)
    assert flops.decoder_train_flops_per_token(cfg, 2048) == pytest.approx(1.0193e9, rel=1e-4)


def _recorded():
    """Two asks of a traced live-ask window on one TPU v5e."""
    from bench.trace import op_name

    fx = json.load(open(os.path.join(os.path.dirname(__file__), "data", "ask_trace.json")))
    ops = {0: [(op_name(n), s, e) for n, s, e in fx["ops"]]}
    return fx, TraceView(ops, {0: fx["modules"]}, fx["spans"], tuple(fx["window"]), [0])


def test_recorded_trace_busy_time_and_kernel_calls():
    fx, v = _recorded()
    lo, hi = fx["window"]
    clipped = sorted((max(s, lo), min(e, hi)) for _, s, e in fx["ops"])
    # one TensorCore: operations never overlap, so the union is their sum
    assert all(a[1] <= b[0] for a, b in zip(clipped, clipped[1:]))
    assert v.busy_s() == pytest.approx(sum(e - s for s, e in clipped) * 1e-9)
    kernel = [(s, e) for n, s, e in fx["ops"] if "custom-call(" in n and "_parzen_padded" in n.split(" = ")[0]]
    n, secs = v.op_seconds(lambda name: "parzen" in name)
    assert n == len(kernel) == 14
    assert secs == pytest.approx(sum(e - s for s, e in kernel) * 1e-9)
    top = dict(v.top_ops(10))
    assert top["jit__parzen_padded/_parzen_padded.1"] == pytest.approx(secs)


def test_recorded_trace_gaps_are_the_asks():
    _, v = _recorded()
    gaps = dict(v.idle_gaps(10))
    idle = v.window_s - v.busy_s()
    assert sum(gaps.values()) == pytest.approx(idle)
    assert gaps["ask"] > 0.9 * idle  # the host sat in the asks' fit while the chip waited
