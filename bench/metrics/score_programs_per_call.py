"""Device programs (the trace's ``XLA Modules`` events) that start inside a
``tpe.score`` span, per span: what one scoring round trip runs on the chip
besides the kernel (conversions, pads, the slice)."""

from bench import spans


def read(r):
    s = spans.load()
    return None if s is None else s.programs_per_span(r.trace, "tpe.score")
