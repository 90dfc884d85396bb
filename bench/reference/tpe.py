"""Univariate TPE's fit in plain numpy: the two Parzen mixtures one ask of a
parameter scores its candidates against, built from the finished history.

Written from the published algorithm (Bergstra et al., NeurIPS 2011) with
the defaults of Optuna (KDD 2019):

- split: the observations sorted by loss, ties in trial order; the best
  ``min(ceil(0.1 n), 25)`` are ``below`` and the rest ``above``;
- weights: over the ``n`` observations in trial order, the 25 most recent
  weigh 1 and the older ones ramp linearly from ``1 / n`` to 1;
- each side: a Gaussian truncated to ``[low, high]`` at every observation,
  and a prior at the middle of the range, of width ``high - low`` and
  weight 1.  Components are sorted by location; each one's width is the
  larger gap to its neighbours (the bounds stand in at the ends), the
  prior's is the range, and all are clipped to ``[(high - low) / min(100,
  1 + k), high - low]`` for ``k`` components (the magic clip).  Weights are
  normalised.

A component's ``log_norm`` is its log weight less the log of its truncated
normal's normaliser, so the mixture's log-density at ``x`` is the
log-sum-exp of ``-(x - mu)^2 / (2 sigma^2) + log_norm``
(``parzen.mixture_log_pdf``).
"""

from __future__ import annotations

import math

import numpy as np
from scipy.special import ndtr


def n_below(n: int) -> int:
    return min(int(math.ceil(0.1 * n)), 25)


def recency_weights(n: int) -> np.ndarray:
    if n < 25:
        return np.ones(n)
    return np.concatenate([np.linspace(1.0 / n, 1.0, n - 25), np.ones(25)])


def split(xs, losses) -> tuple:
    """``(below, above, w_below, w_above)`` of one parameter's observations
    ``xs``, in trial order, with their ``losses``."""
    xs, losses = np.asarray(xs, np.float64), np.asarray(losses, np.float64)
    order = np.argsort(losses, kind="stable")
    w = recency_weights(len(xs))
    b, a = order[: n_below(len(xs))], order[n_below(len(xs)):]
    return xs[b], xs[a], w[b], w[a]


def parzen(xs, weights, low: float, high: float) -> tuple:
    """``(mus, sigmas, log_norm)`` of one side's mixture, with its prior."""
    span = high - low
    mus = np.append(np.asarray(xs, np.float64), 0.5 * (low + high))
    w = np.append(np.asarray(weights, np.float64), 1.0)
    prior = np.zeros(len(mus), bool)
    prior[-1] = True
    order = np.argsort(mus)
    mus, w, prior = mus[order], w[order], prior[order]
    k = len(mus)
    if k == 1:
        sigmas = np.array([span])
    else:
        edges = np.concatenate([[low], mus, [high]])
        sigmas = np.maximum(mus - edges[:-2], edges[2:] - mus)
    sigmas[prior] = span
    sigmas = np.clip(sigmas, span / min(100.0, 1.0 + k), span)
    w = w / w.sum()
    mass = ndtr((high - mus) / sigmas) - ndtr((low - mus) / sigmas)
    log_norm = np.log(w) - np.log(sigmas) - 0.5 * math.log(2 * math.pi) - np.log(mass)
    return mus, sigmas, log_norm


def fit(xs, losses, low: float, high: float) -> tuple:
    """The ``below`` and ``above`` mixtures of one parameter's ask."""
    below, above, w_below, w_above = split(xs, losses)
    return parzen(below, w_below, low, high), parzen(above, w_above, low, high)
