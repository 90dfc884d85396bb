"""Telemetry backbone: registry semantics, spans, trial event trace, and the
server metrics surface (ISSUE 6 acceptance)."""

import os
import threading
import time

import pytest

import repro.core as hpo
from repro.core import telemetry
from repro.core.telemetry import (
    EV_COMPLETED,
    EV_CREATED,
    EV_PRUNED,
    EV_REPORTED,
    EVENT_KINDS,
    Counter,
    Histogram,
    MetricsRegistry,
    TrialEventLog,
    _iter_event_tuples,
)


@pytest.fixture(autouse=True)
def _clean_registry():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


# -- instruments ---------------------------------------------------------------


class TestInstruments:
    def test_counter_threadsafe(self):
        c = Counter("c")
        threads = [
            threading.Thread(target=lambda: [c.inc() for _ in range(1000)])
            for _ in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 8000

    def test_histogram_percentiles(self):
        h = Histogram("h")
        for ms in range(1, 101):  # 1ms .. 100ms uniform
            h.observe(ms / 1e3)
        s = h.summary()
        assert s["count"] == 100
        assert s["min"] == pytest.approx(1e-3)
        assert s["max"] == pytest.approx(0.1)
        # uniform 1..100ms: p50 ~ 50ms, p95 ~ 95ms, p99 ~ 99ms within one
        # geometric bucket (10/decade -> ~26% wide) of the true value
        assert 0.03 < s["p50"] < 0.07
        assert 0.07 < s["p95"] < 0.1
        assert 0.08 < s["p99"] <= 0.1
        assert s["p50"] <= s["p95"] <= s["p99"] <= s["max"]

    def test_histogram_empty_and_overflow(self):
        h = Histogram("h")
        assert h.summary()["p99"] == 0.0
        h.observe(1e9)  # beyond the top bound -> overflow bucket
        assert h.summary()["p99"] == pytest.approx(1e9)
        assert h.summary()["max"] == pytest.approx(1e9)

    def test_gauge(self):
        r = MetricsRegistry()
        g = r.gauge("g")
        g.set(3.0)
        g.add(-1.0)
        assert g.value == 2.0


# -- registry / module-level helpers ------------------------------------------


class TestRegistry:
    def test_disabled_is_noop(self):
        assert not telemetry.enabled()
        telemetry.inc("x")
        telemetry.observe("y", 0.5)
        with telemetry.span("z"):
            pass
        snap = telemetry.snapshot()
        assert snap == {"counters": {}, "gauges": {}, "histograms": {}}

    def test_disabled_span_is_shared_noop(self):
        s1 = telemetry.span("a")
        s2 = telemetry.span("b")
        assert s1 is s2  # one shared _NOOP object, no allocation per call

    def test_enabled_records(self):
        telemetry.enable()
        telemetry.inc("ops", 3)
        telemetry.inc("ops")
        with telemetry.span("lat"):
            time.sleep(0.01)
        snap = telemetry.snapshot()
        assert snap["counters"]["ops"] == 4
        h = snap["histograms"]["lat"]
        assert h["count"] == 1
        assert 0.005 < h["mean"] < 1.0  # the sleep is timed, roughly

    def test_reset(self):
        telemetry.enable()
        telemetry.inc("x")
        telemetry.reset()
        assert telemetry.snapshot()["counters"] == {}

    def test_snapshot_json_safe(self):
        import json

        telemetry.enable()
        telemetry.inc("a")
        telemetry.set_gauge("b", 1.5)
        telemetry.observe("c", 0.01)
        json.dumps(telemetry.snapshot())  # must not raise

    def test_worker_context(self):
        default = telemetry.worker_id()
        assert ":" in default
        telemetry.set_worker_context("1.2.3.4:555")
        try:
            assert telemetry.worker_id() == "1.2.3.4:555"
        finally:
            telemetry.set_worker_context(None)
        assert telemetry.worker_id() == default


# -- trial event log -----------------------------------------------------------


class TestEventLog:
    def test_append_and_rows(self):
        log = TrialEventLog()
        log.append(EV_CREATED, 0, worker="w0")
        log.append(EV_REPORTED, 0, step=3, worker="w0")
        log.append(EV_COMPLETED, 0, worker="w1")
        rows = log.rows()
        assert [r["event"] for r in rows] == ["created", "reported", "completed"]
        assert rows[1]["step"] == 3
        assert rows[0]["worker"] == "w0" and rows[2]["worker"] == "w1"
        # monotonic timestamps
        assert rows[0]["t_ns"] <= rows[1]["t_ns"] <= rows[2]["t_ns"]

    def test_growth_past_initial_capacity(self):
        log = TrialEventLog()
        for i in range(300):
            log.append(EV_CREATED, i, worker="w")
        assert len(log) == 300
        assert [r["number"] for r in log.rows()] == list(range(300))

    def test_incremental_snapshot(self):
        log = TrialEventLog()
        for i in range(5):
            log.append(EV_CREATED, i, worker="w")
        snap = log.snapshot(since=3)
        assert snap["since"] == 3 and snap["next"] == 5
        assert snap["number"] == [3, 4]
        # a since past the end is clamped, not an error
        assert log.snapshot(since=99)["kind"] == []

    def test_storage_hosts_event_log(self):
        st = hpo.InMemoryStorage()
        s = hpo.create_study(storage=st, pruner=hpo.NopPruner())

        def obj(t):
            t.suggest_float("x", 0, 1)
            t.report(1.0, 0)
            return 1.0

        s.optimize(obj, n_trials=3)
        snap = st.get_trial_events(s._study_id)
        kinds = [EVENT_KINDS[k] for k in snap["kind"]]
        assert kinds.count("created") == 3
        assert kinds.count("reported") == 3
        assert kinds.count("completed") == 3
        # delete_study drops the trace
        st.delete_study(s._study_id)
        assert st.get_trial_events(s._study_id)["kind"] == []


# -- remote round trip (acceptance) -------------------------------------------


def _run_seeded_study(storage):
    s = hpo.create_study(
        study_name="trace",
        storage=storage,
        sampler=hpo.RandomSampler(seed=7),
        pruner=hpo.MedianPruner(n_startup_trials=2, n_warmup_steps=0),
    )

    def obj(t):
        x = t.suggest_float("x", 0, 1)
        for step in range(3):
            t.report(x + step * 0.1, step)
            if t.should_prune():
                raise hpo.TrialPruned()
        return x

    s.optimize(obj, n_trials=12)
    return s._study_id


class TestRemoteRoundTrip:
    def test_event_trace_survives_remote_protocol(self):
        """The remote run must reconstruct the exact (event, number, step)
        sequence an inmemory run of the same seeded study produces."""
        mem = hpo.InMemoryStorage()
        local_sid = _run_seeded_study(mem)
        local = list(_iter_event_tuples(mem.get_trial_events(local_sid)))

        backend = hpo.InMemoryStorage()
        with hpo.StorageServer(backend) as server:
            remote = hpo.RemoteStorage(server.url)
            remote_sid = _run_seeded_study(remote)
            wire = remote.get_trial_events(remote_sid)
        assert list(_iter_event_tuples(wire)) == local
        # worker ids on the server-recorded trace are the client peers
        assert all(w.count(":") == 1 for w in wire["workers"])

    def test_get_server_metrics_rpc(self):
        backend = hpo.InMemoryStorage()
        with hpo.StorageServer(backend) as server:
            remote = hpo.RemoteStorage(server.url)
            _run_seeded_study(remote)
            m = remote.get_server_metrics()
            m2 = server.get_server_metrics()
        assert m["frames_in"] > 0 and m["bytes_in"] > 0
        assert m["frames_out"] > 0 and m["bytes_out"] > 0
        methods = m["methods"]
        assert "create_new_trial" in methods
        row = methods["create_new_trial"]
        assert row["calls"] == 12 and row["errors"] == 0
        assert row["bytes_out"] > 0
        assert 0 <= row["p50"] <= row["p95"] <= row["p99"] <= row["max"]
        # the in-process accessor serves the same surface
        assert m2["methods"]["create_new_trial"]["calls"] == 12

    def test_client_rpc_spans_when_enabled(self):
        telemetry.enable()
        backend = hpo.InMemoryStorage()
        with hpo.StorageServer(backend) as server:
            remote = hpo.RemoteStorage(server.url)
            sid = remote.create_new_study(
                [hpo.StudyDirection.MINIMIZE], "spans"
            )
            for _ in range(3):
                remote.create_new_trial(sid)
        snap = telemetry.snapshot()
        assert snap["counters"]["client.frames_out"] >= 4
        assert snap["counters"]["client.bytes_out"] > 0
        assert snap["histograms"]["client.rpc.create_new_trial"]["count"] == 3

    def test_cached_storage_counters(self):
        telemetry.enable()
        st = hpo.CachedStorage(hpo.InMemoryStorage())
        sid = st.create_new_study([hpo.StudyDirection.MINIMIZE], "cc")
        tid = st.create_new_trial(sid)
        st.get_trial(tid)  # own RUNNING trial -> cache hit
        snap = telemetry.snapshot()
        assert snap["counters"].get("cached.get_trial.hit_own", 0) >= 1


# -- overhead guard ------------------------------------------------------------


def test_disabled_span_overhead_tiny():
    """The disabled span must be within an order of magnitude of a bare
    function call — the <2% production budget pinned by the benchmark."""
    n = 50_000
    t0 = time.perf_counter_ns()
    for _ in range(n):
        with telemetry.span("x"):
            pass
    per_call = (time.perf_counter_ns() - t0) / n
    assert per_call < 5_000  # ns; generous CI bound, typically ~250ns


# -- spans on the profiler's timeline -----------------------------------------


def test_disabled_span_with_ids_is_the_shared_noop():
    assert telemetry.span("train.compile", trial=3) is telemetry._NOOP
    assert telemetry.span("tpe.score") is telemetry._NOOP


def test_importing_telemetry_imports_no_jax():
    """The storage server records through this module without jax: neither
    the import nor an enabled span may load it."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from repro.core import telemetry\n"
        "telemetry.enable()\n"
        "with telemetry.span('storage.report_and_prune', trial=1):\n"
        "    pass\n"
        "assert telemetry.snapshot()['histograms']['storage.report_and_prune']['count'] == 1\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
    )
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         env={**os.environ, "PYTHONPATH": os.path.abspath(src)}, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]


def _traced_spans(tmp_path, fn, prefixes: tuple) -> list:
    """Run ``fn`` with telemetry enabled under a CPU profiler trace; return
    the host events whose names start with ``prefixes`` as ``(name, start,
    end, line, stats)``, in start order."""
    import glob

    jax = pytest.importorskip("jax")
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    telemetry.enable()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
        telemetry.disable()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    out = []
    for p, plane in enumerate(ProfileData.from_file(path).planes):
        for k, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(prefixes):
                    out.append((e.name, e.start_ns, e.end_ns, (p, k), dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def test_enabled_span_is_on_the_profiler_trace_nested_with_its_trial(tmp_path):
    def work():
        with telemetry.span("scheduler.trial", trial=3):
            with telemetry.span("train.compile"):
                time.sleep(0.002)
        with telemetry.span("train.report"):  # outside any trial
            pass

    spans = _traced_spans(tmp_path, work, ("scheduler.", "train."))
    assert [s[0] for s in spans] == ["scheduler.trial", "train.compile", "train.report"]
    outer, inner, after = spans
    assert inner[3] == outer[3]  # one thread's line
    assert outer[1] <= inner[1] and inner[2] <= outer[2]
    assert outer[4]["trial"] == 3 and inner[4]["trial"] == 3  # inherited
    assert "trial" not in after[4]
    # the histograms are recorded as before
    assert telemetry.snapshot()["histograms"]["train.compile"]["count"] == 1


def test_trainer_records_its_phases_in_order(tmp_path):
    from repro import configs
    from repro.launch.mesh import make_auto_mesh
    from repro.train import SyntheticLM, TrainConfig, Trainer

    jax = pytest.importorskip("jax")
    cfg = configs.get_smoke_config("smollm-135m")
    tcfg = TrainConfig(total_steps=2, eval_every=2, warmup_steps=1)
    mesh = make_auto_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    reports = []

    def run():
        trainer = Trainer(cfg, tcfg, SyntheticLM(cfg, 2, 32), mesh=mesh,
                          report_fn=lambda step, loss: reports.append(step) or False)
        with telemetry.span("scheduler.trial", trial=7):
            trainer.run()

    spans = _traced_spans(tmp_path, run, ("train.",))
    assert [s[0] for s in spans] == [
        "train.init", "train.batch", "train.compile", "train.batch", "train.dispatch",
        "train.loss_sync", "train.report",
    ]
    assert all(s[4].get("trial") == 7 for s in spans)
    assert reports == [2]


def test_device_tpe_ask_records_the_scoring_round_trip(tmp_path):
    """On the Pallas engine (interpret mode on the CPU) each parameter's
    score is put on the device, launched and fetched, inside ``tpe.score``."""
    study = hpo.create_study(sampler=hpo.TPESampler(seed=0, n_startup_trials=5, engine="pallas"))
    study.optimize(lambda t: t.suggest_float("x", -1, 1) ** 2, n_trials=6)

    def ask():
        trial = study.ask()
        trial.suggest_float("x", -1, 1)

    spans = _traced_spans(tmp_path, ask, ("tpe.", "parzen.", "trial."))
    names = [s[0] for s in spans]
    assert names == ["trial.suggest", "tpe.fit", "tpe.estimate", "tpe.score",
                     "parzen.prepare", "parzen.launch", "tpe.score.fetch"]
    score = spans[names.index("tpe.score")]
    for name in ("parzen.prepare", "parzen.launch", "tpe.score.fetch"):
        s = spans[names.index(name)]
        assert score[1] <= s[1] and s[2] <= score[2]
    assert all(s[4].get("trial") == 6 for s in spans)
