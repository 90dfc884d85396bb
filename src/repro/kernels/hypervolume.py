"""Monte-Carlo hypervolume counting kernel (Pallas, TPU target).

The many-objective (m > 4) path of ``core/moo.py``'s
``HypervolumeEstimator``: exact WFG recursion blows up combinatorially in m,
so hypervolume and per-point exclusive contributions are estimated by
uniform sampling inside the bounding box ``[min(points), reference]``.  The
kernel streams sample tiles against the full (VMEM-resident) point set and
accumulates, per sample tile,

* ``total``  — how many samples are dominated by >= 1 point
  (``hv ~ box_volume * total / n_samples``), and
* ``excl[i]`` — how many samples are dominated by point ``i`` *alone*
  (``contribution_i ~ box_volume * excl[i] / n_samples`` — the exclusive
  region ``hv(all) - hv(all minus i)`` in expectation).

Counts accumulate as f32 (exact up to 2^24 — far above any sane sample
budget).  Points are padded to a power-of-two count with ``+1e30``
coordinates (they dominate nothing), samples to a block multiple with
``-1e30`` (dominated by nothing), so padding never perturbs a count and XLA
retraces O(log n) times.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ops

__all__ = ["mc_hv_kernel", "mc_hv_counts"]

BIG = 1e30


def mc_hv_kernel(
    pts_ref,  # in: [N, M] full point set (loss orientation), points on sublanes
    smp_ref,  # in: [M, bs] one sample tile, samples on lanes
    excl_ref,  # out: [N, 1] exclusive-domination counts
    tot_ref,  # out: [1, bs] dominated-sample count per lane
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        excl_ref[...] = jnp.zeros_like(excl_ref)
        tot_ref[...] = jnp.zeros_like(tot_ref)

    pts = pts_ref[...]
    smp = smp_ref[...]
    # dom[p, s]: point p dominates sample s (<= in every objective; ties
    # count — a measure-zero set under continuous sampling).  Unrolled over
    # the few objectives so every comparison is one 2-D [N, bs] tile.
    dom = pts[:, 0:1] <= smp[0:1, :]
    for k in range(1, pts.shape[1]):
        dom = dom & (pts[:, k:k + 1] <= smp[k:k + 1, :])
    domf = dom.astype(jnp.float32)
    cnt = jnp.sum(domf, axis=0, keepdims=True)  # [1, bs] dominating points per sample
    tot_ref[...] += (cnt > 0.0).astype(jnp.float32)
    only = (cnt == 1.0).astype(jnp.float32)
    excl_ref[...] += jnp.sum(domf * only, axis=1, keepdims=True)


#: elements of one [points, samples] domination tile; the default sample
#: block keeps the kernel's few tile-sized temporaries inside scoped VMEM
_TILE_ELEMS = 1 << 18


def default_block_s(n_points: int) -> int:
    """Sample-tile width for ``n_points`` (padded) points: a multiple of the
    128-lane width, at most 1024, and at most ``_TILE_ELEMS`` per tile."""
    return max(128, min(1024, _TILE_ELEMS // max(n_points, 1)))


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def _mc_hv_padded(
    points: jax.Array,  # [n_p, m] pow2-padded
    samples: jax.Array,  # [s_p, m] block-multiple-padded
    *,
    block_s: int,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    ops.bump_trace("pallas.mc_hv")  # traced body: runs once per trace
    n_p, m = points.shape
    ns = samples.shape[0] // block_s
    excl, tot = pl.pallas_call(
        mc_hv_kernel,
        grid=(ns,),
        in_specs=[
            pl.BlockSpec((n_p, m), lambda i: (0, 0)),
            pl.BlockSpec((m, block_s), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((n_p, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, block_s), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_p, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, block_s), jnp.float32),
        ],
        interpret=interpret,
    )(points, samples.T)
    return excl[:, 0], jnp.sum(tot)


def mc_hv_counts(
    points: jax.Array,  # [n, m]
    samples: jax.Array,  # [s, m]
    *,
    block_s: "int | None" = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """``(excl [n] f32, total scalar f32)`` domination counts.

    Padding happens *outside* the jit boundary so the compile cache keys on
    the pow2 bucket, not the raw point count — n in 17..32 shares one trace.
    ``block_s`` defaults to :func:`default_block_s` of the padded count.
    """
    points = jnp.asarray(points, jnp.float32)
    samples = jnp.asarray(samples, jnp.float32)
    n, m = points.shape
    s = samples.shape[0]
    n_p = ops.pad_pow2_len(n)
    if n_p != n:
        points = jnp.pad(points, ((0, n_p - n), (0, 0)), constant_values=BIG)
    block_s = min(block_s or default_block_s(n_p), s)
    s_p = -(-s // block_s) * block_s
    if s_p != s:
        samples = jnp.pad(samples, ((0, s_p - s), (0, 0)), constant_values=-BIG)
    excl, tot = _mc_hv_padded(points, samples, block_s=block_s, interpret=interpret)
    return excl[:n], tot
