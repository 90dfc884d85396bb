"""Milliseconds per ask under the sampler's ``tpe.fit`` (the history's
split) and ``tpe.estimate`` (each parameter's two Parzen estimators)
spans: TPE's host fit."""

from bench import spans


def read(r):
    s = spans.load()
    asks = r.host.get("asks", 0)
    if s is None or not asks or not (s.count("tpe.fit") or s.count("tpe.estimate")):
        return None
    return 1e3 * s.seconds("tpe.fit", "tpe.estimate") / asks
