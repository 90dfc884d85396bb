"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device state
(the dry-run sets XLA_FLAGS *before* any jax initialization; smoke tests must
keep seeing 1 device).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh", "make_auto_mesh", "slice_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16,16) ("data","model") = 256 chips (v5e pod).
    Multi-pod: (2,16,16) ("pod","data","model") = 512 chips; "pod" is a batch
    axis crossing the DCN/inter-pod links."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_host_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over host (CPU) devices for tests."""
    return make_auto_mesh(shape, axes)


def make_auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules and
    ``with_sharding_constraint`` anchors in ``models/`` speak GSPMD, which
    the ``Explicit`` axes that ``jax.make_mesh`` now defaults to refuse."""
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def slice_mesh(mesh, n_slices: int, axis: str = "data"):
    """Split a mesh into ``n_slices`` disjoint sub-meshes along ``axis`` —
    trial-parallel HPO: each concurrent trial trains on one slice (see
    repro.tune.scheduler).  Returns a list of Mesh objects over disjoint
    device subsets."""
    from jax.sharding import Mesh

    devs = mesh.devices  # ndarray [axes...]
    ax = mesh.axis_names.index(axis)
    size = devs.shape[ax]
    assert size % n_slices == 0, (size, n_slices)
    chunk = size // n_slices
    out = []
    for i in range(n_slices):
        sl = [slice(None)] * devs.ndim
        sl[ax] = slice(i * chunk, (i + 1) * chunk)
        out.append(Mesh(devs[tuple(sl)], mesh.axis_names, axis_types=mesh.axis_types))
    return out
