"""Seconds JAX spent tracing, lowering and compiling inside the window, per
trial started there (``jax.monitoring`` compile durations)."""


def read(r):
    n = r.host.get("trials_started", 0)
    return r.host["compile_s"] / n if n else None
