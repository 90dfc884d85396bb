"""Milliseconds per ask under the sampler's ``tpe.score.fetch`` spans: the
wait for the Parzen kernel's scores and their copy to the host, summed
over an ask's parameters."""

from bench import spans


def read(r):
    s = spans.load()
    asks = r.host.get("asks", 0)
    if s is None or not asks or not s.count("tpe.score.fetch"):
        return None
    return 1e3 * s.seconds("tpe.score.fetch") / asks
