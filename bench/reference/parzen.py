"""The TPE acquisition ``log l(x) - log g(x)`` in float64 numpy.

Each mixture is a set of Gaussian components ``(mu, sigma, log_norm)``;
``log_norm`` folds in the component's weight and its truncation to the
parameter's bounds, so a component with ``log_norm = -inf`` (padding)
contributes nothing.  This is the scorer the Parzen kernel must agree with:
every candidate's score, from the same component arrays the kernel was given.

``round_to`` computes every step in a lower-precision type instead (rounding
inputs and each intermediate): the control, the scorer in the precision
below the kernel's float32.
"""

from __future__ import annotations

import numpy as np


def mixture_log_pdf(cands, mus, sigmas, log_norm, dtype=np.float64):
    """Log-density of the mixture at each candidate, computed in ``dtype``.
    The max-shifted exponent is floored at -700, below which a term cannot
    change the sum (whose largest term is 1)."""
    c = np.asarray(cands, dtype)[:, None]
    z = (c - np.asarray(mus, dtype)[None, :]) / np.asarray(sigmas, dtype)[None, :]
    z = np.asarray(-0.5, dtype) * z * z + np.asarray(log_norm, dtype)[None, :]
    m = np.max(z, axis=1)
    shifted = np.maximum(z - m[:, None], np.asarray(-700.0, dtype))
    return m + np.log(np.sum(np.exp(shifted), axis=1, dtype=dtype))


def score(cands, l_mus, l_sigmas, l_log_norm, g_mus, g_sigmas, g_log_norm, round_to=None):
    dtype = np.float64 if round_to is None else round_to
    log_l = mixture_log_pdf(cands, l_mus, l_sigmas, l_log_norm, dtype)
    log_g = mixture_log_pdf(cands, g_mus, g_sigmas, g_log_norm, dtype)
    return (log_l - log_g).astype(np.float64)


def score_error(got, ref) -> float:
    """The largest gap between scores, relative to ``1 + |ref|``."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref) / (1.0 + np.abs(ref))))


def rastrigin(x) -> float:
    """Rastrigin's function (the paper's black-box benchmark suite)."""
    x = np.asarray(x, np.float64)
    return float(10 * len(x) + np.sum(x * x - 10 * np.cos(2 * np.pi * x)))
