"""Device microseconds per call of the Parzen kernel: the trace's Pallas
custom call, which XLA names after the kernel's jitted wrapper
(``_parzen_padded``)."""


def is_parzen(name: str) -> bool:
    return "parzen" in name


def read(r):
    n, s = r.trace.op_seconds(is_parzen)
    return 1e6 * s / n if n else None
