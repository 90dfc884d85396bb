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
with ``log_norm = -inf``) so XLA retraces O(log n_components) times; the
wrapper additionally pads both mixtures to one common length so a single
grid serves the ``l`` and ``g`` sides.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
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


@functools.partial(
    jax.jit, static_argnames=("block_c", "block_k", "interpret")
)
def _parzen_padded(
    cands: jax.Array,  # [C_p] block-multiple-padded
    l_mus, l_sigmas, l_log_norm,  # [K_p] common padded length
    g_mus, g_sigmas, g_log_norm,  # [K_p]
    *,
    block_c: int,
    block_k: int,
    interpret: bool,
) -> jax.Array:
    ops.bump_trace("pallas.parzen")  # traced body: runs once per trace
    C_p, K_p = cands.shape[0], l_mus.shape[0]
    nc, nk = C_p // block_c, K_p // block_k

    # 2-D layouts only: 1-D blocks get a tiling from XLA that Mosaic refuses
    # once a block is smaller than the array.  Components ride the lane axis
    # (one packed DMA per step), candidates the sublane axis.
    filler = jnp.ones_like(l_mus)
    comps = jnp.stack(
        [l_mus, l_sigmas, l_log_norm, g_mus, g_sigmas, g_log_norm, filler, filler]
    )
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
    )(cands.reshape(C_p, 1), comps)
    return out[:, 0]


def parzen_score(
    cands: jax.Array,  # [C]
    l_mus: jax.Array, l_sigmas: jax.Array, l_log_norm: jax.Array,  # [Kl]
    g_mus: jax.Array, g_sigmas: jax.Array, g_log_norm: jax.Array,  # [Kg]
    *,
    block_c: int = 256,
    block_k: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """``log l(cands) - log g(cands)`` as a [C] f32 array.

    All shape normalization (common component length, block-multiple padding)
    happens *outside* the jit boundary, so the compile cache keys on the
    padded shapes: pre-bucketed callers with unequal ``Kl``/``Kg`` (or raw
    callers inside one bucket) share a single trace.  Telemetry times that
    preparation (float32 conversion, padding, host-to-device copies) as
    ``parzen.prepare`` and the kernel's dispatch as ``parzen.launch``.
    """
    with telemetry.span("parzen.prepare"):
        def prep(x):
            return jnp.asarray(x, jnp.float32)

        cands = prep(cands)
        C = cands.shape[0]
        K = max(l_mus.shape[0], g_mus.shape[0])

        def pad_side(mus, sigmas, ln):
            k = mus.shape[0]
            if k < K:
                mus = jnp.pad(prep(mus), (0, K - k))
                sigmas = jnp.pad(prep(sigmas), (0, K - k), constant_values=1.0)
                ln = jnp.pad(prep(ln), (0, K - k), constant_values=NEG_INF)
                return mus, sigmas, ln
            return prep(mus), prep(sigmas), prep(ln)

        l_side = pad_side(l_mus, l_sigmas, l_log_norm)
        g_side = pad_side(g_mus, g_sigmas, g_log_norm)

        block_c = min(block_c, C)
        block_k = min(block_k, K)
        C_p = -(-C // block_c) * block_c
        K_p = -(-K // block_k) * block_k
        if C_p != C:
            cands = jnp.pad(cands, (0, C_p - C))
        if K_p != K:
            pad = (0, K_p - K)

            def pad_tail(side):
                mus, sigmas, ln = side
                return (
                    jnp.pad(mus, pad),
                    jnp.pad(sigmas, pad, constant_values=1.0),
                    jnp.pad(ln, pad, constant_values=NEG_INF),
                )

            l_side, g_side = pad_tail(l_side), pad_tail(g_side)
    with telemetry.span("parzen.launch"):
        out = _parzen_padded(
            cands, *l_side, *g_side,
            block_c=block_c, block_k=block_k, interpret=interpret,
        )
        return out[:C]
