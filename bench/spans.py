"""The program's own spans in a traced window, on the device's clock.

With telemetry enabled (a traced run), every ``telemetry.span`` the program
opens is also a ``jax.profiler.TraceAnnotation`` on its thread's line of
the host plane: ``scheduler.trial``, ``train.*``, ``study.*``,
``trial.suggest``, ``tpe.*``, ``parzen.*``, ``storage.*``, ``client.*``.
This module reads them from the run's xplane, beside the device lines that
``trace.py`` reads: seconds, counts and self time (a span minus its
children) of the spans that start inside the window, the window's idle
device time that no program span covers, and device programs per span.

The trace is parsed once per run and kept here, so each reader of
``metrics/`` can call ``load()``.  It returns ``None`` where the window
holds no program span (a program that does not annotate its spans), and
the reader then reports nothing.

    python3 bench/spans.py [<trace dir>]

prints each span name's count, seconds and self seconds in the window of
the last traced run.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys
from collections import defaultdict

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402
from bench.trace import WINDOW_MARKS, _device_id, merge  # noqa: E402

#: names the program gives its spans (``core/telemetry.py``'s convention,
#: ``component.operation``); the host plane's other events are the runtime's
PROGRAM_SPAN = re.compile(r"(scheduler|train|study|trial|tpe|parzen|storage|client)\.[a-z_.]+")


class Spans:
    """Program spans ``(name, start_ns, end_ns, thread)`` that start inside
    ``window``; ``thread`` tells the host lines apart."""

    def __init__(self, spans, window: tuple):
        lo, hi = window
        self.window = window
        self.spans = sorted((s for s in spans if lo <= s[1] < hi), key=lambda s: (s[1], -s[2]))

    def __len__(self) -> int:
        return len(self.spans)

    @classmethod
    def from_xplane(cls, path: str) -> "Spans":
        from jax.profiler import ProfileData

        spans, marks = [], {}
        for p, plane in enumerate(ProfileData.from_file(path).planes):
            if _device_id(plane.name) is not None:
                continue
            for k, line in enumerate(plane.lines):
                for e in line.events:
                    if PROGRAM_SPAN.fullmatch(e.name):
                        spans.append((e.name, e.start_ns, e.end_ns, (p, k)))
                    elif e.name in WINDOW_MARKS:
                        marks[e.name] = e.start_ns
        if len(marks) != 2:
            raise RuntimeError(f"the window marks {WINDOW_MARKS} are not both in {path}")
        return cls(spans, (marks[WINDOW_MARKS[0]], marks[WINDOW_MARKS[1]]))

    # -- per name -------------------------------------------------------------------------

    def count(self, name: str) -> int:
        return sum(1 for s in self.spans if s[0] == name)

    def seconds(self, *names: str) -> float:
        """Seconds under the spans named ``names``, each instant counted
        once per thread (a span nested in another of ``names`` adds
        nothing)."""
        per_thread = defaultdict(list)
        for name, s, e, thread in self.spans:
            if name in names:
                per_thread[thread].append((s, e))
        return sum(e - s for iv in per_thread.values() for s, e in merge(iv)) * 1e-9

    def self_seconds(self) -> dict:
        """Seconds of each span name minus the spans nested directly in it
        on its thread, by name."""
        out: dict = defaultdict(float)
        by_thread = defaultdict(list)
        for span in self.spans:
            by_thread[span[3]].append(span)
        for spans in by_thread.values():
            stack: list = []  # [name, end, self ns] of the open spans
            for name, s, e, _ in spans:  # sorted by start, outer first
                while stack and stack[-1][1] <= s:
                    closed = stack.pop()
                    out[closed[0]] += closed[2] * 1e-9
                if stack:
                    stack[-1][2] -= min(e, stack[-1][1]) - s
                stack.append([name, e, e - s])
            for name, _, ns in stack:
                out[name] += ns * 1e-9
        return dict(out)

    # -- against the device ---------------------------------------------------------------

    def covered(self) -> list:
        """The union of every program span, clipped to the window."""
        lo, hi = self.window
        return merge((max(s, lo), min(e, hi)) for _, s, e, _ in self.spans)

    def untraced_idle_s(self, view) -> float:
        """Seconds of the window in which the device is idle and no program
        span is open on any host thread, averaged over ``view``'s devices."""
        covered = self.covered()
        total = 0
        for d in view.devices:
            gaps = view.gaps(d)
            total += sum(e - s for s, e in gaps) - _overlap_ns(gaps, covered)
        return total * 1e-9 / max(1, len(view.devices))

    def programs_per_span(self, view, name: str):
        """Device programs (``view``'s ``XLA Modules`` events, all devices)
        that start inside a span named ``name``, per such span."""
        n = self.count(name)
        if not n:
            return None
        inside = merge((s, e) for nm, s, e, _ in self.spans if nm == name)
        starts = [s for s, _ in inside]
        hits = 0
        for d in view.devices:
            for _, s, _ in view.modules[d]:
                i = bisect.bisect_right(starts, s) - 1
                hits += i >= 0 and s < inside[i][1]
        return hits / n


def _overlap_ns(a: list, b: list) -> int:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def trace_file(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file under {trace_dir}, found {files}")
    return files[0]


_cache: dict = {}


def load() -> "Spans | None":
    """The program spans in the window of the run's trace (under
    ``harness.TRACE_DIR``); ``None`` where there are none."""
    path = trace_file(harness.TRACE_DIR)
    key = (path, os.stat(path).st_mtime_ns)
    if key not in _cache:
        _cache.clear()
        spans = Spans.from_xplane(path)
        _cache[key] = spans if len(spans) else None
    return _cache[key]


def main(argv: list) -> int:
    spans = Spans.from_xplane(trace_file(argv[0] if argv else harness.TRACE_DIR))
    own = spans.self_seconds()
    lo, hi = spans.window
    print(f"window {(hi - lo) * 1e-9:.6f} s, {len(spans)} program spans")
    print(f"{'span':<28}{'count':>8}{'seconds':>14}{'self s':>14}")
    for name in sorted(own, key=lambda n: -spans.seconds(n)):
        print(f"{name:<28}{spans.count(name):>8}{spans.seconds(name):>14.6f}{own[name]:>14.6f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
