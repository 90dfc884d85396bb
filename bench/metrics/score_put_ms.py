"""Milliseconds per ask under the kernel wrapper's ``parzen.prepare``
spans: float32 conversion, padding and host-to-device copies of the Parzen
kernel's seven arrays, summed over an ask's parameters."""

from bench import spans


def read(r):
    s = spans.load()
    asks = r.host.get("asks", 0)
    if s is None or not asks or not s.count("parzen.prepare"):
        return None
    return 1e3 * s.seconds("parzen.prepare") / asks
