"""Share of the window in which the device is idle and no program span is
open on any host thread, averaged over the chips: idle time that the
program's spans do not name.  It serves ``idle_untraced_pct.<cell kind>``
of every cell whose program annotates its spans."""

from bench import spans


def read(r):
    s = spans.load()
    if s is None:
        return None
    return 100.0 * s.untraced_idle_s(r.trace) / r.trace.window_s
