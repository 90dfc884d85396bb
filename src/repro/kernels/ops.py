"""Shared engine policy + jit'd public wrappers around the Pallas kernels.

Two layers live here:

**Engine policy (numpy-only, import-free).**  The sampler stack
(``samplers/tpe.py``, ``core/moo.py``) dispatches every hot reduction through
:func:`resolve_engine`: ``engine="auto"`` picks the device path once the
problem crosses a work threshold (and jax imports), ``"numpy"``/``"jax"``/
``"pallas"`` force a path.  Device inputs are padded to power-of-two buckets
(:func:`pad_pow2_vec` / :func:`pad_pow2_rows`) so the set of shapes XLA ever
sees — and hence the number of retraces — stays logarithmic in the
observation count; the shared trace registry (:func:`bump_trace` /
:func:`trace_count`) is what the retrace-bound tests pin.  Importing this
module does **not** import jax: the policy helpers are pure numpy, and the
jitted wrappers below are materialized lazily via module ``__getattr__``.

**Kernel wrappers (lazy, jax-importing).**  On a TPU backend the kernels
lower natively through Mosaic, and nothing runs in interpret mode; on any
other backend they run with ``interpret=True`` (Pallas executes the kernel
body with the XLA interpreter — the same semantics, no Mosaic), which is how
the CPU tests check them.  The sampler engine calls the Parzen and MC-HV
kernels when :func:`pallas_enabled` (a TPU backend, or ``REPRO_USE_PALLAS=1``
for the CPU tests).  No model layer calls the attention, SSD, sLSTM or
cross-entropy kernels: the models use their pure-jnp chunked forms on every
backend.
"""

from __future__ import annotations

import os
import threading

import numpy as np

__all__ = [
    # engine policy
    "MIN_PAD",
    "TPE_JIT_THRESHOLD",
    "DOM_JIT_THRESHOLD",
    "DOM_CPU_CEILING",
    "SCORE_TABLE_SIZE",
    "jax_available",
    "resolve_engine",
    "validate_engine",
    "pad_pow2_len",
    "pad_pow2_vec",
    "pad_pow2_rows",
    "bump_trace",
    "trace_count",
    "reset_traces",
    # kernel wrappers (lazy)
    "flash_attention_op",
    "ssd_op",
    "crossentropy_op",
    "slstm_op",
    "parzen_score_op",
    "mc_hv_counts_op",
    "should_interpret",
    "pallas_enabled",
]

# -- pow2 padding ---------------------------------------------------------------

#: smallest padded bucket — below this every input shares one trace
MIN_PAD = 8


def pad_pow2_len(n: int, min_pad: int = MIN_PAD) -> int:
    """Next power-of-two bucket >= ``n`` (floored at ``min_pad``)."""
    size = min_pad
    while size < n:
        size *= 2
    return size


def pad_pow2_vec(vec: np.ndarray, fill: float, min_pad: int = MIN_PAD) -> np.ndarray:
    """Pad a 1-D array to its power-of-two bucket with ``fill``.

    Device mixtures pad with ``log_norm = -inf`` (or a large-negative finite
    sentinel inside Pallas kernels): padding components contribute
    ``exp(-inf) = 0`` to the logsumexp row sums, so the score is exactly the
    unpadded one while the shape only changes at power-of-two crossings."""
    n = len(vec)
    size = pad_pow2_len(n, min_pad)
    if size == n:
        return vec
    out = np.full(size, fill, dtype=vec.dtype if vec.dtype.kind == "f" else float)
    out[:n] = vec
    return out


def pad_pow2_rows(arr2d: np.ndarray, fill: float, min_pad: int = MIN_PAD) -> np.ndarray:
    """Pad a ``(n, d)`` array to a power-of-two row count with ``fill``."""
    n = len(arr2d)
    size = pad_pow2_len(n, min_pad)
    if size == n:
        return arr2d
    out = np.full((size, arr2d.shape[1]), fill)
    out[:n] = arr2d
    return out


# -- trace registry ---------------------------------------------------------------

_trace_lock = threading.Lock()
_trace_counts: dict[str, int] = {}


def bump_trace(key: str) -> None:
    """Record one XLA trace for ``key`` — call from *inside* the traced
    python body, which runs once per trace, not per call.  Tests pin these
    counts to prove pow2 bucketing bounds retracing."""
    with _trace_lock:
        _trace_counts[key] = _trace_counts.get(key, 0) + 1


def trace_count(key: str) -> int:
    with _trace_lock:
        return _trace_counts.get(key, 0)


def reset_traces(key: "str | None" = None) -> None:
    with _trace_lock:
        if key is None:
            _trace_counts.clear()
        else:
            _trace_counts.pop(key, None)


# -- engine resolution ------------------------------------------------------------

ENGINES = ("auto", "numpy", "jax", "pallas")

#: auto-engine work thresholds: below these the numpy path wins outright
#: (device dispatch overhead dominates).  TPE work = n_candidates x
#: n_components (both estimators); dominance work = n_rows x n_objectives.
TPE_JIT_THRESHOLD = 16384
DOM_JIT_THRESHOLD = 4096
#: the jax dominance reduction materializes the full (n, n, m) comparison
#: cube; off-TPU, cap auto-dispatch so host memory stays bounded
DOM_CPU_CEILING = 64 * 1024
#: grid resolution of the TPE device score table (see samplers/tpe.py)
SCORE_TABLE_SIZE = 4096

_jax_probe: "bool | None" = None


def jax_available() -> bool:
    """Cached jax import probe — one import attempt per process."""
    global _jax_probe
    if _jax_probe is None:
        try:
            import jax  # noqa: F401

            _jax_probe = True
        except Exception:
            _jax_probe = False
    return _jax_probe


def validate_engine(engine: str) -> str:
    if engine not in ENGINES:
        raise ValueError(f"engine must be one of {ENGINES}, got {engine!r}")
    return engine


def resolve_engine(
    engine: str,
    work: int,
    threshold: int,
    ceiling: "int | None" = None,
) -> str:
    """Resolve a requested engine to a concrete path for one call site.

    ``"numpy"`` and explicit ``"jax"``/``"pallas"`` pass through (the caller
    is responsible for falling back — with a logged reason — when jax is
    unavailable).  ``"auto"`` picks the device past ``threshold`` units of
    work (``pallas`` when :func:`pallas_enabled`, else plain jit), staying on
    numpy below it, when jax is missing, or past ``ceiling`` on non-TPU
    backends (memory-bound reductions only)."""
    validate_engine(engine)
    if engine != "auto":
        return engine
    if work < threshold or not jax_available():
        return "numpy"
    if ceiling is not None and work > ceiling:
        import jax

        if jax.default_backend() != "tpu":
            return "numpy"
    return "pallas" if pallas_enabled() else "jax"


# -- pallas / interpret switches (lazy jax import) --------------------------------


def should_interpret() -> bool:
    import jax

    return jax.default_backend() != "tpu"


def pallas_enabled() -> bool:
    if os.environ.get("REPRO_USE_PALLAS") == "1":
        return True
    if not jax_available():
        return False
    import jax

    return jax.default_backend() == "tpu"


# -- lazy jitted kernel wrappers --------------------------------------------------
#
# Building these eagerly would make ``import repro.core`` pay the jax import
# (the sampler stack imports this module for the policy helpers alone).  PEP
# 562 module __getattr__ materializes each wrapper on first access and caches
# it in the module dict, so ``from repro.kernels.ops import crossentropy_op``
# keeps working unchanged.


def _build_flash_attention_op():
    import functools

    import jax

    from .flash_attention import flash_attention

    @functools.partial(
        jax.jit, static_argnames=("causal", "window", "softcap", "block_q", "block_k")
    )
    def flash_attention_op(
        q, k, v, causal: bool = True, window: int = -1, softcap: float = 0.0,
        block_q: int = 512, block_k: int = 512,
    ):
        """q: [B, Hq, S, D]; k/v: [B, Hkv, S, D] -> [B, Hq, S, D]."""
        return flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap,
            block_q=block_q, block_k=block_k, interpret=should_interpret(),
        )

    return flash_attention_op


def _build_ssd_op():
    import functools

    import jax

    from .ssd import ssd

    @functools.partial(jax.jit, static_argnames=("chunk",))
    def ssd_op(x, dt, A, Bm, Cm, chunk: int = 128):
        """Folded-head SSD: x [BH,S,P], dt [BH,S], A [BH], Bm/Cm [BH,S,N]."""
        return ssd(x, dt, A, Bm, Cm, chunk=chunk, interpret=should_interpret())

    return ssd_op


def _build_slstm_op():
    import functools

    import jax

    from .slstm import slstm_scan

    @functools.partial(jax.jit, static_argnames=("batch_tile",))
    def slstm_op(u, R, batch_tile: int = 8):
        """Fused sLSTM scan: u [S,B,4,H,D], R [4,H,D,D] -> (h_seq, final states)."""
        return slstm_scan(u, R, batch_tile=batch_tile, interpret=should_interpret())

    return slstm_op


def _build_crossentropy_op():
    import functools

    import jax

    from .crossentropy import fused_crossentropy

    @functools.partial(jax.jit, static_argnames=("softcap", "block_t", "block_v"))
    def crossentropy_op(
        x, w, labels, softcap: float = 0.0, block_t: int = 256, block_v: int = 1024
    ):
        """Fused per-token NLL: x [T,D], w [D,V], labels [T] -> [T] f32."""
        return fused_crossentropy(
            x, w, labels, softcap=softcap, block_t=block_t, block_v=block_v,
            interpret=should_interpret(),
        )

    return crossentropy_op


def _build_parzen_score_op():
    from .parzen import parzen_score

    def parzen_score_op(cands, l_mus, l_sigmas, l_log_norm, g_mus, g_sigmas, g_log_norm):
        """Fused Parzen ``log l - log g`` over candidates (Pallas; interpret
        mode off-TPU).  Component arrays should arrive pow2-padded.  The 7
        host arrays are packed in numpy into the kernel's two operands and
        put on the device at once, so a call runs one device program."""
        return parzen_score(
            cands, l_mus, l_sigmas, l_log_norm, g_mus, g_sigmas, g_log_norm,
            interpret=should_interpret(),
        )

    return parzen_score_op


def _build_mc_hv_counts_op():
    from .hypervolume import mc_hv_counts

    def mc_hv_counts_op(points, samples):
        """MC hypervolume counts (Pallas; interpret mode off-TPU): per-point
        exclusive-domination counts + total dominated count."""
        return mc_hv_counts(points, samples, interpret=should_interpret())

    return mc_hv_counts_op


_LAZY_OPS = {
    "flash_attention_op": _build_flash_attention_op,
    "ssd_op": _build_ssd_op,
    "slstm_op": _build_slstm_op,
    "crossentropy_op": _build_crossentropy_op,
    "parzen_score_op": _build_parzen_score_op,
    "mc_hv_counts_op": _build_mc_hv_counts_op,
}


def __getattr__(name: str):
    builder = _LAZY_OPS.get(name)
    if builder is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    op = builder()
    globals()[name] = op  # cache: __getattr__ fires only on the first miss
    return op
