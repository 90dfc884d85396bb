"""Host milliseconds per ask outside the device scoring: the client's ask
(``Study.ask`` and every ``suggest_float``) minus the program's ``tpe.score``
spans in it, per ask."""


def read(r):
    h = r.host
    if not h.get("asks") or not h.get("score_calls"):
        return None
    return 1e3 * (h["ask_s"] - h["score_s"]) / h["asks"]
