"""From a profiler trace to device numbers: busy time, time per operation or
per program, and idle gaps named by what the host was doing.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Each device is a plane named ``/device:<KIND>:<id>``; its ``XLA Ops`` line
holds one event per operation executed, named by the operation's HLO text
(``%fusion.12 = f32[...] fusion(...)``, kept here as ``fusion.12``), its
``XLA Modules`` line one event per program executed (``jit_step(<hash>)``).  The benchmark's own host spans
(``jax.profiler.TraceAnnotation`` named ``bench.*``) sit on the host plane,
on the same clock; ``bench.window_open`` and ``bench.window_close`` mark the
measured window.  Everything here counts only what lies inside that window.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_MARKS = ("bench.window_open", "bench.window_close")
SPAN_PREFIX = "bench."


def merge(intervals) -> list:
    """The union of ``(start, end)`` intervals, sorted and disjoint."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class TraceView:
    """Device events and host spans of one traced window.

    ``ops`` and ``modules`` map a device id to its ``(name, start_ns,
    end_ns)`` events; ``spans`` lists the host's ``(name, start_ns, end_ns)``;
    ``window`` is ``(start_ns, end_ns)``."""

    def __init__(self, ops: dict, modules: dict, spans: list, window: tuple, devices: list):
        self.window = window
        self.devices = list(devices)
        lo, hi = window
        self.ops = {d: self._clip(ops.get(d, ())) for d in self.devices}
        self.modules = {d: self._clip(modules.get(d, ())) for d in self.devices}
        self.spans = sorted(
            (s for s in spans if s[0] not in WINDOW_MARKS and s[2] > lo and s[1] < hi),
            key=lambda s: s[1],
        )
        self._starts = [s[1] for s in self.spans]
        self._reach = []  # the latest end among spans[: i + 1]
        for s in self.spans:
            self._reach.append(max(s[2], self._reach[-1]) if self._reach else s[2])

    def _clip(self, events) -> list:
        lo, hi = self.window
        return [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    # -- reading a trace file ------------------------------------------------------------

    @classmethod
    def from_dir(cls, trace_dir: str, devices: list) -> "TraceView":
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file under {trace_dir}, found {files}")
        return cls.from_xplane(files[0], devices)

    @classmethod
    def from_xplane(cls, path: str, devices: list) -> "TraceView":
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        ops: dict = defaultdict(list)
        modules: dict = defaultdict(list)
        spans: list = []
        marks: dict = {}
        for plane in data.planes:
            dev = _device_id(plane.name)
            for line in plane.lines:
                if dev is not None and line.name == OPS_LINE:
                    ops[dev].extend((op_name(e.name), e.start_ns, e.end_ns) for e in line.events)
                elif dev is not None and line.name == MODULES_LINE:
                    modules[dev].extend((e.name, e.start_ns, e.end_ns) for e in line.events)
                elif dev is None:
                    for e in line.events:
                        if e.name.startswith(SPAN_PREFIX):
                            spans.append((e.name, e.start_ns, e.end_ns))
                            if e.name in WINDOW_MARKS:
                                marks[e.name] = e.start_ns
        if len(marks) != 2:
            raise RuntimeError(f"the window marks {WINDOW_MARKS} are not both in {path}")
        window = (marks[WINDOW_MARKS[0]], marks[WINDOW_MARKS[1]])
        return cls(ops, modules, spans, window, devices)

    # -- device time -----------------------------------------------------------------------

    def busy_intervals(self, dev: int) -> list:
        return merge((s, e) for _, s, e in self.ops[dev])

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        total = sum(e - s for d in self.devices for s, e in self.busy_intervals(d))
        return total * 1e-9 / max(1, len(self.devices))

    def op_seconds(self, match) -> tuple:
        """``(events, seconds)`` of the operations whose name ``match``es,
        over all devices."""
        n, ns = 0, 0
        for d in self.devices:
            for name, s, e in self.ops[d]:
                if match(name):
                    n += 1
                    ns += e - s
        return n, ns * 1e-9

    def module_runs(self, match) -> list:
        """Seconds of each execution of the programs whose name ``match``es."""
        return [
            (e - s) * 1e-9
            for d in self.devices for name, s, e in self.modules[d] if match(name)
        ]

    def top_ops(self, k: int) -> list:
        """The ``k`` operations with the most device time, each named
        ``<program>/<operation>``, with seconds."""
        tot: dict = defaultdict(int)
        for d in self.devices:
            mods = sorted(self.modules[d], key=lambda m: m[1])
            i = 0
            for name, s, e in sorted(self.ops[d], key=lambda o: o[1]):
                while i < len(mods) and mods[i][2] <= s:
                    i += 1
                inside = i < len(mods) and mods[i][1] <= s
                prog = mods[i][0].split("(")[0] if inside else "?"
                tot[f"{prog}/{name}"] += e - s
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns * 1e-9] for name, ns in top]

    # -- idle time -------------------------------------------------------------------------

    def gaps(self, dev: int) -> list:
        """Idle ``(start, end)`` intervals of ``dev`` inside the window."""
        lo, hi = self.window
        out, t = [], lo
        for s, e in self.busy_intervals(dev):
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if hi > t:
            out.append((t, hi))
        return out

    def host_activity(self, t: int) -> str:
        """The latest-started benchmark span covering host time ``t`` (the
        innermost one, where spans nest), without its prefix."""
        for i in range(bisect.bisect_right(self._starts, t) - 1, -1, -1):
            if self._reach[i] <= t:
                break
            name, _, end = self.spans[i]
            if end > t:
                return name[len(SPAN_PREFIX):]
        return "other"

    def idle_gaps(self, k: int) -> list:
        """Idle device time summed by what the host was doing at each gap's
        middle, the ``k`` largest, averaged over the devices."""
        tot: dict = defaultdict(int)
        for d in self.devices:
            for s, e in self.gaps(d):
                tot[self.host_activity((s + e) // 2)] += e - s
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        n = max(1, len(self.devices))
        return [[name, ns * 1e-9 / n] for name, ns in top]


def op_name(hlo_text: str) -> str:
    """``fusion.12`` for ``%fusion.12 = f32[8] fusion(...)``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def _device_id(plane_name: str):
    """``3`` for ``/device:TPU:3``; ``None`` for host planes."""
    if not plane_name.startswith("/device:") or plane_name.startswith("/device:CPU"):
        return None
    tail = plane_name.rsplit(":", 1)[-1]
    return int(tail) if tail.isdigit() else None
