"""The program's spans read from a traced window: span seconds, counts and
self time, idle device time no span names, programs per span, and the
readers built on them."""

import os
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402
from bench import harness, spans  # noqa: E402
from bench.trace import TraceView  # noqa: E402

MS = 1_000_000  # ns
MAIN, OTHER = (0, 0), (0, 1)  # two host threads


def _trace():
    """A [0, 100) ms window on one device, busy in [10, 12), [30, 31),
    [32, 33) and [60, 70) ms; programs start at 10, 30, 32 and 60 ms.

    Main thread: a trial [5, 80) holding ``train.init`` [5, 20), a
    ``train.compile`` [20, 50) with a ``tpe.score`` [25, 40) in it (which
    holds ``parzen.prepare`` [26, 29) and ``tpe.score.fetch`` [33, 38)), a
    second ``tpe.score`` [55, 58), and a ``train.init`` [90, 95).  Another
    thread: ``tpe.fit`` [82, 86) and ``tpe.estimate`` [84, 88) overlapping
    it, and a ``train.compile`` [-5, 3) that starts before the window."""
    ops = {0: [("fusion.1", 10 * MS, 12 * MS), ("parzen_score.1", 30 * MS, 31 * MS),
               ("copy.1", 32 * MS, 33 * MS), ("fusion.2", 60 * MS, 70 * MS)]}
    modules = {0: [("jit_train_step(1)", 10 * MS, 12 * MS), ("jit__parzen_padded(2)", 30 * MS, 31 * MS),
                   ("jit_convert_element_type(3)", 32 * MS, 33 * MS),
                   ("jit_train_step(1)", 60 * MS, 70 * MS)]}
    view = TraceView(ops, modules, [], (0, 100 * MS), [0])
    program = [
        ("scheduler.trial", 5, 80, MAIN),
        ("train.init", 5, 20, MAIN),
        ("train.compile", 20, 50, MAIN),
        ("tpe.score", 25, 40, MAIN),
        ("parzen.prepare", 26, 29, MAIN),
        ("tpe.score.fetch", 33, 38, MAIN),
        ("tpe.score", 55, 58, MAIN),
        ("train.init", 90, 95, MAIN),
        ("tpe.fit", 82, 86, OTHER),
        ("tpe.estimate", 84, 88, OTHER),
        ("train.compile", -5, 3, OTHER),
    ]
    return view, spans.Spans([(n, s * MS, e * MS, t) for n, s, e, t in program], view.window)


def test_spans_that_start_in_the_window_count():
    _, s = _trace()
    assert len(s) == 10
    assert s.count("train.compile") == 1 and s.count("tpe.score") == 2
    assert s.seconds("train.init") == pytest.approx(20e-3)
    assert s.seconds("tpe.score") == pytest.approx(18e-3)


def test_nested_or_overlapping_names_count_each_instant_once():
    _, s = _trace()
    assert s.seconds("tpe.fit", "tpe.estimate") == pytest.approx(6e-3)  # [82, 88)
    assert s.seconds("train.compile", "tpe.score") == pytest.approx(33e-3)  # [20, 50) and [55, 58)


def test_self_time_is_a_span_minus_its_children():
    _, s = _trace()
    own = s.self_seconds()
    assert own["tpe.score"] == pytest.approx((15 - 3 - 5 + 3) * 1e-3)
    assert own["train.compile"] == pytest.approx((30 - 15) * 1e-3)
    assert own["scheduler.trial"] == pytest.approx((75 - 15 - 30 - 3) * 1e-3)
    assert own["parzen.prepare"] == pytest.approx(3e-3)
    # overlapping spans on one thread: the later one is the child, for the overlap
    assert own["tpe.fit"] == pytest.approx(2e-3) and own["tpe.estimate"] == pytest.approx(4e-3)


def test_untraced_idle_is_idle_time_outside_every_program_span():
    view, s = _trace()
    # idle: [0, 10), [12, 30), [31, 32), [33, 60), [70, 100); spans cover
    # [5, 80), [82, 88), [90, 95): uncovered idle is [0, 5), [80, 82),
    # [88, 90) and [95, 100)
    assert s.untraced_idle_s(view) == pytest.approx(14e-3)


def test_programs_that_start_inside_a_span_per_span():
    view, s = _trace()
    assert s.programs_per_span(view, "tpe.score") == pytest.approx(1.0)  # 2 programs, 2 spans
    assert s.programs_per_span(view, "train.init") == pytest.approx(0.5)  # the one at 10 ms
    assert s.programs_per_span(view, "parzen.launch") is None


def _read(metric, host, monkeypatch):
    view, s = _trace()
    monkeypatch.setattr(spans, "load", lambda: s)
    reader = harness.load_module(harness.reader_path(metric))
    return reader.read(SimpleNamespace(trace=view, host=host, peaks={}))


@pytest.mark.parametrize(
    "metric,host,want",
    [
        ("trial_compile_s", {"trials_started": 2}, 15e-3),
        ("trial_init_s", {"trials_started": 2}, 10e-3),
        ("idle_untraced_pct.trials", {}, 14.0),
        ("idle_untraced_pct.ask", {}, 14.0),
        ("score_put_ms", {"asks": 2}, 1.5),
        ("score_fetch_ms", {"asks": 2}, 2.5),
        ("score_programs_per_call", {"asks": 2}, 1.0),
        ("ask_fit_ms", {"asks": 2}, 3.0),
    ],
)
def test_reader_values(metric, host, want, monkeypatch):
    assert _read(metric, host, monkeypatch) == pytest.approx(want)


@pytest.mark.parametrize(
    "metric",
    ["trial_compile_s", "trial_init_s", "idle_untraced_pct.trials", "score_put_ms",
     "score_fetch_ms", "score_programs_per_call", "ask_fit_ms"],
)
def test_readers_report_nothing_without_program_spans(metric, monkeypatch):
    """A program that does not annotate its spans (the trace holds none)."""
    view = TraceView({0: []}, {0: []}, [], (0, MS), [0])
    monkeypatch.setattr(spans, "load", lambda: None)
    reader = harness.load_module(harness.reader_path(metric))
    r = SimpleNamespace(trace=view, host={"asks": 3, "trials_started": 1}, peaks={})
    assert reader.read(r) is None


def test_load_reads_a_cpu_trace_and_finds_nothing_without_program_spans(tmp_path, monkeypatch):
    jax = pytest.importorskip("jax")
    from repro.core import telemetry

    def traced(annotate: bool):
        trace_dir = tmp_path / ("with" if annotate else "without")
        monkeypatch.setattr(harness, "TRACE_DIR", str(trace_dir))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        with jax.profiler.TraceAnnotation(harness.WINDOW_OPEN):
            pass
        if annotate:
            telemetry.enable()
        try:
            with telemetry.span("scheduler.trial", trial=4):
                with telemetry.span("train.compile"):
                    jax.numpy.ones(4).block_until_ready()
        finally:
            telemetry.disable()
            telemetry.reset()
        with jax.profiler.TraceAnnotation(harness.WINDOW_CLOSE):
            pass
        jax.profiler.stop_trace()
        return spans.load()

    assert traced(annotate=False) is None
    s = traced(annotate=True)
    assert [n for n, *_ in s.spans] == ["scheduler.trial", "train.compile"]
    assert s.seconds("train.compile") > 0
    assert set(s.self_seconds()) == {"scheduler.trial", "train.compile"}


def test_tiny_traced_ask_reports_the_new_metrics(monkeypatch, tmp_path):
    """A traced run of the tiny live-ask cell on the CPU (Pallas in interpret
    mode) reports every new metric of its cell."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    tiny.use_checkout(monkeypatch, tiny.make_checkout(str(tmp_path / "checkout")))
    code, line, err = tiny.run_cell("tiny-tpe.live-ask", trace=1, seconds=1.0)
    assert code == 0 and line["correct"], err[-2000:]
    m = line["metrics"]
    for name in ("idle_untraced_pct.ask", "score_put_ms", "score_fetch_ms",
                 "score_programs_per_call", "ask_fit_ms"):
        assert name in m, (name, sorted(m))
    assert m["score_put_ms"]["value"] + m["score_fetch_ms"]["value"] <= m["score_roundtrip_ms"]["value"]
