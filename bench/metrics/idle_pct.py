"""Share of the window in which no operation ran on the device, averaged
over the chips (1 - busy union / window, from the trace).  It serves
``idle_pct.<cell kind>`` of every cell."""


def read(r):
    return 100.0 * (1.0 - r.trace.busy_s() / r.trace.window_s)
