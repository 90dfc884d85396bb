"""Milliseconds per ask inside the program's ``tpe.score`` spans: the
sampler engine's calls to the device and back, summed over an ask's
parameters."""


def read(r):
    h = r.host
    if not h.get("asks") or not h.get("score_calls"):
        return None
    return 1e3 * h["score_s"] / h["asks"]
