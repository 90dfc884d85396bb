"""Mean milliseconds of a report in the window: the benchmark's span around
``Trial.report`` and ``Trial.should_prune`` (the fused report-and-prune call
into in-memory storage)."""


def read(r):
    n = r.host.get("reports", 0)
    return 1e3 * r.host["report_s"] / n if n else None
