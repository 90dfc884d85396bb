"""Successive halving's prune decision, as the paper's Algorithm 1 states it
(Akiba et al., KDD 2019, Alg. 1), minimising.

A trial reporting ``value`` at ``step`` is looked at only on a rung,
``step == r * eta ** (s + rung)`` with ``rung = max(0, floor(log_eta(step //
r)) - s)``.  There it survives when ``value`` is among the best
``max(1, n // eta)`` of the ``n`` values reported at that step so far by the
trials that are complete, pruned or running, itself included; a NaN never
survives a rung.
"""

from __future__ import annotations

import math


def prunes(value: float, step: int, peers: list, r: int, eta: int, s: int = 0) -> bool:
    """Whether the trial is pruned; ``peers``: the other trials' values at
    ``step`` (NaN ones are left out)."""
    if step < r:
        return False
    rung = max(0, int(math.log(step // r, eta)) - s)
    if step != r * eta ** (s + rung):
        return False
    if value != value:
        return True
    values = [v for v in peers if v == v] + [value]
    k = max(1, len(values) // eta)
    return not value <= sorted(values)[k - 1]
