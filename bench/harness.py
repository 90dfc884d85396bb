"""The one general runner behind ``bench/run.py``.

It finds the cell by name, checks the device, points JAX's persistent
compilation cache at the checkout, hands the cell to its driver
(``drivers/<driver>.py``), and prints one JSON line.  With ``--trace 0`` the
line carries the cell's end-to-end metrics; with ``--trace 1`` the window
runs under the JAX profiler and each per-layer metric is computed by its own
reader (``reader_path``) from the trace, the program's spans and counters,
and what the driver timed.  A reader that finds nothing returns ``None``
and its metric is left out of the line.

Every run checks what its timed path produced (the driver's ``checks``) and
prints each compared number beside its limit, on standard error last and
under the result line's last key, ``checks``.
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import importlib.util
import json
import math
import os
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
#: run outputs (the profiler trace, the window's compile cache); gitignored
OUT_DIR = os.path.join(ROOT, ".bench_out")
#: the checkout's persistent compile cache, a fixed path (the path is part
#: of every entry's key); gitignored
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: the window's own compile cache, a fixed path: every run deletes from it
#: all entries but those of programs that do not depend on a trial's
#: settings, so no other program the window compiles is served from an entry
#: an earlier run wrote (a cache entry's key includes its directory, so
#: entries cannot be copied in from elsewhere)
WINDOW_CACHE_DIR = os.path.join(OUT_DIR, "window_cache")
TRACE_DIR = os.path.join(OUT_DIR, "trace")
#: zero-length host spans that mark the window in the trace
WINDOW_OPEN, WINDOW_CLOSE = "bench.window_open", "bench.window_close"

#: JAX's compile-phase duration events (``jax.monitoring``)
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
BACKEND_COMPILE = COMPILE_EVENTS[2]
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def sub_seed(seed: int, tag: str) -> int:
    """A 31-bit seed for one use of ``--seed`` (any whole number)."""
    digest = hashlib.sha256(f"{seed}:{tag}".encode()).digest()
    return int.from_bytes(digest[:4], "little") & 0x7FFFFFFF


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def reader_path(metric: str) -> str:
    """The reader of a per-layer metric: ``metrics/<name>.py``, or, for a
    name with a suffix such as ``step_ms.trials``, the base's
    ``metrics/step_ms.py`` where the suffix has no file of its own."""
    path = os.path.join(BENCH, "metrics", f"{metric}.py")
    base = metric.rsplit(".", 1)[0]
    if not os.path.exists(path) and base != metric:
        path = os.path.join(BENCH, "metrics", f"{base}.py")
    return path


def load_module(path: str):
    """Import one file of the benchmark (drivers and metric readers)."""
    name = "bench_" + os.path.relpath(path, BENCH).replace(os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the cell ---------------------------------------------------------------------------


class Cell:
    """One entry of ``workloads`` with its workload and configuration files."""

    def __init__(self, manifest: dict, name: str):
        entries = {w["name"]: w for w in manifest["workloads"]}
        if name not in entries:
            raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has {sorted(entries)}")
        self.entry = entries[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in manifest["configs"]}
        self.config = load_json(os.path.join(ROOT, configs[self.entry["config"]]["file"]))
        self.workload = load_json(os.path.join(BENCH, "workloads", f"{name}.json"))
        self.end_to_end = [m for m in manifest["end_to_end"] if self._applies(m)]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            m for m in manifest["per_layer"]
            if m["moves"] in reported and self._applies(m)
        ]

    def _applies(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]


# -- the device ---------------------------------------------------------------------------


def check_device(chips: int) -> dict:
    """The accelerator JAX runs on; raises ``NoAccelerator`` off the chip."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform == "cpu":
        raise NoAccelerator(f"JAX found no accelerator (platform {platform!r})")
    if len(devices) < chips:
        raise NoAccelerator(f"{len(devices)} {platform} devices, the cell needs {chips}")
    return {"platform": platform, "kind": devices[0].device_kind, "count": len(devices)}


def load_peaks(kind: str) -> dict:
    """The published peaks of ``kind``; an unknown device is an error."""
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in bench/peaks.json ({sorted(table)})")
    return table[kind]


# -- compile accounting ----------------------------------------------------------------


class CompileLog:
    """JAX's compile-phase durations, summed by phase of the run, and its
    persistent-cache hits."""

    def __init__(self):
        import jax

        self.phase = "setup"
        self.seconds: dict = {}
        self.compiles: dict = {}
        self.cache_hits: dict = {}
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kwargs) -> None:
        if event == CACHE_HIT:
            with self._lock:
                self.cache_hits[self.phase] = self.cache_hits.get(self.phase, 0) + 1

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if event not in COMPILE_EVENTS:
            return
        with self._lock:
            self.seconds[self.phase] = self.seconds.get(self.phase, 0.0) + duration
            if event == BACKEND_COMPILE:
                self.compiles[self.phase] = self.compiles.get(self.phase, 0) + 1


def _set_cache_dir(path: str) -> None:
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_compilation_cache_dir", path)
    compilation_cache.reset_cache()


# -- one run ------------------------------------------------------------------------------


class Run:
    """What a driver gets: the cell, its seed and window length, the device,
    the window's clock, the tracer and the compile log."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 device: dict, peaks: dict, t_process: float):
        import jax

        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.device = device
        self.peaks = peaks
        self.t_process = t_process
        self.devices = jax.devices()[: cell.chips]
        self.compile_log = CompileLog()
        self.t_open = self.t_close = None
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        self.use_checkout_cache()

    def sub_seed(self, tag: str) -> int:
        return sub_seed(self.seed, tag)

    # compile caches: set-up and the reference use the checkout's; the window
    # its own, emptied here, so a repeated seed finds nothing an earlier run
    # compiled
    def use_checkout_cache(self) -> None:
        _set_cache_dir(CACHE_DIR)

    def use_window_cache(self, keep: tuple = ()) -> None:
        """The window's compile cache, holding only the entries of the
        programs named in ``keep`` (prefixes of entry names, such as
        ``"jit_init-"``): programs that do not depend on a trial's settings."""
        os.makedirs(WINDOW_CACHE_DIR, exist_ok=True)
        for name in os.listdir(WINDOW_CACHE_DIR):
            if not (keep and name.startswith(keep)):
                os.remove(os.path.join(WINDOW_CACHE_DIR, name))
        _set_cache_dir(WINDOW_CACHE_DIR)

    def annotate(self, name: str):
        """A host span in the profiler's trace (traced runs only)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def open_window(self, keep: tuple = ()) -> float:
        """Start the measured window: from here on the window's own compile
        cache (``use_window_cache(keep)``), and in a traced run the profiler
        (device and host spans, no Python function tracing) and the
        program's telemetry."""
        self.use_window_cache(keep)
        if self.trace:
            import jax

            from repro.core import telemetry

            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            telemetry.enable()
            telemetry.reset()
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
            with jax.profiler.TraceAnnotation(WINDOW_OPEN):
                pass
        self.compile_log.phase = "window"
        self.t_open = time.perf_counter()
        return self.t_open

    def close_window(self) -> float:
        self.t_close = time.perf_counter()
        self.compile_log.phase = "after"
        if self.trace:
            import jax

            from repro.core import telemetry

            with jax.profiler.TraceAnnotation(WINDOW_CLOSE):
                pass
            jax.profiler.stop_trace()
            self.telemetry = telemetry.snapshot()
            telemetry.disable()
        return self.t_close

    @property
    def deadline(self) -> float:
        return self.t_open + self.seconds

    @property
    def setup_s(self) -> float:
        return self.t_open - self.t_process

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open


class Outcome:
    """What a driver hands back.

    ``e2e``: end-to-end metric values by name.  ``checks``: ``(name, value,
    limit)`` of each compared number (``value <= limit`` passes).
    ``readings``: host-side sums the per-layer readers use."""

    def __init__(self, e2e: dict, attempted: int, failed: int, checks: list,
                 readings: dict, memory_peak_bytes: int, notes: "dict | None" = None):
        self.e2e = e2e
        self.attempted = attempted
        self.failed = failed
        self.checks = checks
        self.readings = readings
        self.memory_peak_bytes = memory_peak_bytes
        self.notes = notes or {}


def memory_peak_bytes(devices, program_peak: int = 0) -> int:
    """The fullest chip's peak as the allocator reports it, or the window's
    step program's own peak (``memory_analysis``) where that is larger."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices]
    return max(peaks + [int(program_peak)])


class Readings:
    """What a per-layer metric reader sees."""

    def __init__(self, run: Run, outcome: Outcome, trace):
        self.peaks = run.peaks
        self.host = outcome.readings
        self.trace = trace


def _fmt(v: float) -> str:
    return repr(float(v))


def main(argv: "list[str] | None" = None, t_process: "float | None" = None) -> int:
    t_process = time.perf_counter() if t_process is None else t_process
    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = Cell(manifest, args.workload)
    try:
        device = check_device(cell.chips)
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    peaks = load_peaks(device["kind"])
    driver = load_module(os.path.join(BENCH, "drivers", f"{cell.workload['driver']}.py"))
    run = Run(cell, args.seed, args.seconds, bool(args.trace), device, peaks, t_process)
    outcome = driver.run(run)

    metrics: dict = {}
    dev = dict(device, memory_peak_bytes=outcome.memory_peak_bytes)
    breakdown = None
    if not args.trace:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(outcome.e2e[m["name"]]), "unit": m["unit"]}
    else:
        from bench import trace as tr

        view = tr.TraceView.from_dir(TRACE_DIR, [d.id for d in run.devices])
        readings = Readings(run, outcome, view)
        for m in cell.per_layer:
            reader = load_module(reader_path(m["name"]))
            value = reader.read(readings)
            if value is not None and math.isfinite(value):
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        dev["busy_s"] = view.busy_s()
        dev["window_s"] = view.window_s
        breakdown = {"device_ops": view.top_ops(10), "idle_gaps": view.idle_gaps(10)}

    compiles = run.compile_log.compiles.get("window", 0)
    print(
        f"bench: setup_s {_fmt(run.setup_s)} window_s {_fmt(run.window_s)} "
        f"compiles in window {compiles} "
        f"({_fmt(run.compile_log.seconds.get('window', 0.0))} s, "
        f"{run.compile_log.cache_hits.get('window', 0)} cache hits) "
        f"attempted {outcome.attempted} failed {outcome.failed} "
        + " ".join(f"{k} {v}" for k, v in outcome.notes.items()),
        file=sys.stderr, flush=True,
    )
    checks = {name: {"value": float(v), "limit": float(lim)} for name, v, lim in outcome.checks}
    correct = bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values()
    )
    for name, c in checks.items():
        print(f"check {name} {_fmt(c['value'])} limit {_fmt(c['limit'])}", file=sys.stderr, flush=True)
    line = {
        "correct": correct,
        "attempted": int(outcome.attempted),
        "failed": int(outcome.failed),
        "metrics": metrics,
        "device": dev,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    print(json.dumps(line), flush=True)
    return 0
