"""Fused Parzen-score kernel (Pallas, TPU target).

Computes the TPE acquisition ``log l(x) - log g(x)`` for a batch of
candidates against two truncated-Gaussian mixtures *in one pass*: the kernel
tiles candidates over the grid's first axis and streams both component sets
through the innermost axis with an online (m, l) logsumexp accumulator per
side — the ``(n_cands, n_components)`` exponent matrix the numpy path
materializes never exists.  This is the large-candidate scorer behind the
TPE device engine's score table (``SCORE_TABLE_SIZE`` grid points per call)
and any ask wave with many pending trials.

Component arrays arrive padded to power-of-two buckets (``ops.pad_pow2_vec``
with ``log_norm = -inf``) so XLA retraces O(log n_components) times.  The
wrapper packs every input on the host in numpy (float32 cast, both mixtures
padded to one common block-multiple length in a single ``[8, K_p]`` block so
one grid serves the ``l`` and ``g`` sides, candidates as a ``[C_p, 1]``
column), puts the two arrays on the device with one ``jax.device_put``, and
runs the kernel and its ``[:C]`` slice as one jitted program: a scoring call
is one device program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ops
from ..core import telemetry

__all__ = ["parzen_score_kernel", "parzen_score"]

NEG_INF = -1e30


def parzen_score_kernel(
    c_ref,  # in: [bc, 1] candidates, one per sublane row
    comp_ref,  # in: [8, bk] component rows (see _COMP_ROWS), lane-dense
    out_ref,  # out: [bc, 1] log l - log g
    lm_ref, ll_ref, gm_ref, gl_ref,  # scratch: [bc, 1] online (m, l) per side
    *,
    n_comp_blocks: int,
):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        lm_ref[...] = jnp.full_like(lm_ref, NEG_INF)
        ll_ref[...] = jnp.zeros_like(ll_ref)
        gm_ref[...] = jnp.full_like(gm_ref, NEG_INF)
        gl_ref[...] = jnp.zeros_like(gl_ref)

    c = c_ref[...]

    def accumulate(row, m_ref, l_ref):
        mu = comp_ref[row:row + 1, :]
        sig = comp_ref[row + 1:row + 2, :]
        ln = comp_ref[row + 2:row + 3, :]
        z = (c - mu) / sig  # [bc, bk]: candidates on sublanes, components on lanes
        # padding components carry log_norm = -inf; clamp to a finite
        # sentinel so the online max shift never mixes infinities
        e = jnp.maximum(-0.5 * z * z + ln, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(e, axis=1, keepdims=True))
        l_ref[...] = l_ref[...] * jnp.exp(m_prev - m_new) + jnp.sum(
            jnp.exp(e - m_new), axis=1, keepdims=True
        )
        m_ref[...] = m_new

    accumulate(0, lm_ref, ll_ref)
    accumulate(3, gm_ref, gl_ref)

    @pl.when(ik == n_comp_blocks - 1)
    def _finalize():
        log_l = lm_ref[...] + jnp.log(jnp.maximum(ll_ref[...], 1e-30))
        log_g = gm_ref[...] + jnp.log(jnp.maximum(gl_ref[...], 1e-30))
        out_ref[...] = log_l - log_g


#: sublane rows of the packed component array: (mu, sigma, log_norm) of the
#: below mixture, then of the above mixture, then two rows of padding that
#: round the block up to one (8, 128) f32 tile height
_COMP_ROWS = 8

#: what the padding past a mixture's own components holds, per row of a side:
#: mu 0, sigma 1, log_norm NEG_INF (inert in the logsumexp)
_SIDE_FILL = (0.0, 1.0, NEG_INF)


def _pack(cands, sides, *, block_c: int, block_k: int):
    """The kernel's operands, built on the host in numpy: candidates as a
    ``[C_p, 1]`` float32 column (0 past ``C``) and both mixtures as one
    ``[8, K_p]`` float32 block in ``_COMP_ROWS`` order, each side padded to
    the common block-multiple length with ``_SIDE_FILL`` and the two filler
    rows 1.0.  Returns them with the clamped block sizes; float64 inputs
    are rounded to nearest-even on assignment, as XLA's convert does."""
    C = len(cands)
    K = max(len(side[0]) for side in sides)
    block_c, block_k = min(block_c, C), min(block_k, K)
    C_p = -(-C // block_c) * block_c
    K_p = -(-K // block_k) * block_k
    col = np.zeros((C_p, 1), np.float32)
    col[:C, 0] = np.asarray(cands)
    comps = np.empty((_COMP_ROWS, K_p), np.float32)
    for base, side in zip((0, 3), sides):
        for row, (x, fill) in enumerate(zip(side, _SIDE_FILL), start=base):
            k = len(x)
            comps[row, :k] = np.asarray(x)
            comps[row, k:] = fill
    comps[6:] = 1.0
    return col, comps, block_c, block_k


@functools.partial(
    jax.jit, static_argnames=("n_cands", "block_c", "block_k", "interpret")
)
def _parzen_padded(
    cands: jax.Array,  # [C_p, 1] block-multiple-padded
    comps: jax.Array,  # [8, K_p] rows in _COMP_ROWS order
    *,
    n_cands: int,
    block_c: int,
    block_k: int,
    interpret: bool,
) -> jax.Array:
    ops.bump_trace("pallas.parzen")  # traced body: runs once per trace
    C_p, K_p = cands.shape[0], comps.shape[1]
    nc, nk = C_p // block_c, K_p // block_k

    # 2-D layouts only: 1-D blocks get a tiling from XLA that Mosaic refuses
    # once a block is smaller than the array.  Components ride the lane axis
    # (one packed DMA per step), candidates the sublane axis.
    kernel = functools.partial(parzen_score_kernel, n_comp_blocks=nk)
    cand_spec = pl.BlockSpec((block_c, 1), lambda ic, ik: (ic, 0))
    out = pl.pallas_call(
        kernel,
        grid=(nc, nk),
        in_specs=[
            cand_spec,
            pl.BlockSpec((_COMP_ROWS, block_k), lambda ic, ik: (0, ik)),
        ],
        out_specs=cand_spec,
        out_shape=jax.ShapeDtypeStruct((C_p, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_c, 1), jnp.float32) for _ in range(4)],
        interpret=interpret,
        name="parzen_score",
    )(cands, comps)
    return out[:n_cands, 0]


def parzen_score(
    cands,  # [C]
    l_mus, l_sigmas, l_log_norm,  # [Kl]
    g_mus, g_sigmas, g_log_norm,  # [Kg]
    *,
    block_c: int = 256,
    block_k: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """``log l(cands) - log g(cands)`` as a [C] f32 array.

    Inputs are numpy arrays (or jax arrays, copied back to the host).  One
    call runs one device program: the host packs the candidates and both
    mixtures in numpy (``_pack``: float32 cast, common component length,
    block-multiple padding) and puts the two operands on the device with a
    single ``jax.device_put``, timed as ``parzen.prepare``; the jitted
    kernel and its ``[:C]`` slice are dispatched as ``parzen.launch``.  The
    compile cache keys on the padded shapes and ``C``, so pre-bucketed
    callers with unequal ``Kl``/``Kg`` share a single trace.
    """
    with telemetry.span("parzen.prepare"):
        col, comps, block_c, block_k = _pack(
            cands,
            ((l_mus, l_sigmas, l_log_norm), (g_mus, g_sigmas, g_log_norm)),
            block_c=block_c, block_k=block_k,
        )
        col, comps = jax.device_put((col, comps))
    with telemetry.span("parzen.launch"):
        return _parzen_padded(
            col, comps, n_cands=len(cands),
            block_c=block_c, block_k=block_k, interpret=interpret,
        )
