"""Seconds per trial started in the window under the trainer's
``train.init`` spans: the optimizer, the sharded initialiser (built, loaded
from the compile cache and dispatched) and any checkpoint restore."""

from bench import spans


def read(r):
    s = spans.load()
    n = r.host.get("trials_started", 0)
    if s is None or not n or not s.count("train.init"):
        return None
    return s.seconds("train.init") / n
