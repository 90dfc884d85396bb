"""Logical-axis sharding: every parameter/activation declares *logical* axes;
a rules table maps them to mesh axes (GSPMD).  Divisibility is checked at
apply time — a logical axis whose size does not divide the assigned mesh axes
falls back to replication (e.g. kv_heads=4 on a 16-way "model" axis).
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = [
    "ShardingRules",
    "TRAIN_RULES",
    "SERVE_RULES",
    "logical_to_spec",
    "logical_to_sharding",
    "tree_shardings",
    "with_logical_constraint",
]

Axes = "str | tuple[str, ...] | None"


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """logical axis name -> mesh axis (or tuple of mesh axes, or None)."""

    rules: dict

    def mesh_axes(self, logical: str | None) -> "tuple[str, ...]":
        if logical is None:
            return ()
        ax = self.rules.get(logical)
        if ax is None:
            return ()
        return (ax,) if isinstance(ax, str) else tuple(ax)


# Production rules. "pod" and "data" are both batch axes; "model" is the
# tensor/expert axis.  fsdp: weight 'embed' dims are additionally sharded over
# the batch axes for ZeRO-3-style memory scaling (GSPMD inserts the
# all-gathers).  Rules intentionally over-specify: missing mesh axes (e.g. no
# "pod" on the single-pod mesh) are filtered out at spec build time.
TRAIN_RULES = ShardingRules(
    rules={
        "batch": ("pod", "data"),
        # Megatron-style sequence parallelism: between layers, activations are
        # sharded over the model axis along seq; GSPMD all-gathers k/v inside
        # attention.  This divides the scan-over-layers residual stack (the
        # dominant train-memory term) by the TP degree.
        "seq": "model",
        "embed": None,
        "fsdp_embed": ("pod", "data"),  # weights' d_model dim under FSDP
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "mlp": "model",
        "vocab": "model",
        "experts": "model",
        "expert_group": None,
        "kv_lora": None,
        "conv": None,
        "state": None,
        "layers": None,
        "stage": "stage",  # only present on pipeline meshes
        "kv_seq": None,
    }
)

# Serving: no gradient/optimizer memory pressure -> keep weights replicated
# over the batch axes (fsdp off) to avoid per-step all-gathers; batch still
# over ("pod","data"); long-context decode shards the KV cache sequence dim
# over the batch axes (batch==1 cells).
SERVE_RULES = ShardingRules(
    rules={
        **TRAIN_RULES.rules,
        "seq": None,  # no residual stack to shard; keep activations whole
        "fsdp_embed": None,
        "kv_seq": ("pod", "data"),
        # caches whose head count does not divide the model axis (musicgen 24H,
        # gemma2 kv=8, tinyllama kv=4) shard the head_dim / MLA latent instead —
        # attention contracts these dims, GSPMD inserts the partial-sum
        # all-reduce (cheap at decode batch sizes).
        "head_dim": "model",
        "kv_lora": "model",
    }
)


def logical_to_spec(
    logical: Sequence[str | None],
    shape: Sequence[int],
    mesh: Mesh,
    rules: ShardingRules,
) -> PartitionSpec:
    """Build a PartitionSpec, dropping mesh axes that are absent, already
    used, or do not divide the dimension."""
    used: set[str] = set()
    spec: list[Any] = []
    for dim, name in zip(shape, logical):
        axes = []
        for ax in rules.mesh_axes(name):
            if ax not in mesh.shape or ax in used:
                continue
            group = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
            if dim % (group * mesh.shape[ax]) != 0:
                continue
            axes.append(ax)
            used.add(ax)
        if not axes:
            spec.append(None)
        elif len(axes) == 1:
            spec.append(axes[0])
        else:
            spec.append(tuple(axes))
    # trim trailing Nones
    while spec and spec[-1] is None:
        spec.pop()
    return PartitionSpec(*spec)


def logical_to_sharding(
    logical: Sequence[str | None],
    shape: Sequence[int],
    mesh: Mesh,
    rules: ShardingRules,
) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(logical, shape, mesh, rules))


def tree_shardings(shape_tree, logical_tree, mesh: Mesh, rules: ShardingRules):
    """Map a pytree of ShapeDtypeStructs + parallel tree of logical axes to
    NamedShardings."""
    return jax.tree.map(
        lambda s, log: logical_to_sharding(log, s.shape, mesh, rules),
        shape_tree,
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
    )


def with_logical_constraint(x, logical: Sequence[str | None], mesh: Mesh | None, rules: ShardingRules):
    """Activation sharding hint (no-op when no mesh is active)."""
    if mesh is None or mesh.empty:
        return x
    sharding = logical_to_sharding(logical, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, sharding)


# -- ambient activation-sharding context ------------------------------------------
#
# Model code is pure and mesh-agnostic; launchers activate a (mesh, rules)
# context at trace time and the layers call ``constrain`` to anchor activation
# shardings (batch over ("pod","data"), experts over "model", ...).  Without
# these anchors GSPMD can propagate a *replicated* batch through the layer
# scan — catastrophic for memory (verified on the smollm dry-run: 409 GiB/dev
# before anchors, ~1 GiB after).  The context is per thread: concurrent
# trials trace their steps on their own slices' meshes at the same time.

_LOCAL = threading.local()


def _active() -> list:
    stack = getattr(_LOCAL, "stack", None)
    if stack is None:
        stack = _LOCAL.stack = []
    return stack


class activation_sharding:
    def __init__(self, mesh: Mesh, rules: ShardingRules):
        self.pair = (mesh, rules)

    def __enter__(self):
        _active().append(self.pair)
        return self

    def __exit__(self, *exc):
        _active().pop()
        return False


def constrain(x, logical: Sequence[str | None]):
    """Sharding anchor using the ambient (mesh, rules); identity when absent."""
    stack = _active()
    if not stack:
        return x
    mesh, rules = stack[-1]
    return with_logical_constraint(x, logical, mesh, rules)


def wrap_with_sharding_ctx(fn, mesh: Mesh, rules: ShardingRules):
    """Make ``fn`` trace (and thus jit-compile) inside the activation-sharding
    context.  The wrapper keeps ``fn``'s name, which names the jitted
    program (``jit_train_step``)."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with activation_sharding(mesh, rules):
            return fn(*args, **kwargs)

    return wrapped
