"""Seconds per trial started in the window under the trainer's
``train.compile`` spans: its first step call, in which JAX traces, lowers
and compiles the step, writes its cache entry and dispatches it.  The same
layer as ``trial_jit_s``, timed from inside the trainer."""

from bench import spans


def read(r):
    s = spans.load()
    n = r.host.get("trials_started", 0)
    if s is None or not n or not s.count("train.compile"):
        return None
    return s.seconds("train.compile") / n
