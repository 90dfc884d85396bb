"""GPipe-style pipeline parallelism with ``shard_map`` + ``lax.ppermute``.

At >512-chip scale (or >400B params) DP×TP alone stops fitting; this module
provides the PP axis: layers are striped across a ``stage`` mesh axis and
microbatches stream through with point-to-point ``ppermute`` transfers — no
all-gathers on the critical path.

Schedule (standard GPipe, M microbatches over P stages):

  for t in 0 .. M+P-2:          # pipeline ticks
      every stage: if it holds a live microbatch, run its layer slice
      ppermute activations stage i -> i+1

Bubble fraction = (P-1)/(M+P-1); EXPERIMENTS.md §Perf quantifies when PP
beats pure DP×TP on the v5e roofline for the assigned models (short answer:
not at ≤512 chips for ≤235B — which is why the production dry-run meshes use
DP×TP(×EP); PP is validated on small host meshes in tests/test_pipeline.py).
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipelined_apply", "make_pp_train_step"]


def pipelined_apply(
    stage_fn: Callable,  # (stage_params, x) -> x  — one stage's layer slice
    params,  # pytree with leading dim = n_stages on every leaf
    x,  # [M, mb, ...] microbatched activations
    mesh: Mesh,
    stage_axis: str = "stage",
):
    """Run x through all stages in pipeline order.  Inside shard_map each
    device holds params for its stage (leading dim 1) and circulates
    microbatch activations."""
    n_stages = mesh.shape[stage_axis]
    M = x.shape[0]

    def body(stage_params, xs):
        stage_params = jax.tree.map(lambda a: a[0], stage_params)  # [1,...] -> [...]
        idx = jax.lax.axis_index(stage_axis)
        mb, feat = xs.shape[1], xs.shape[2:]
        state = jnp.zeros((mb, *feat), xs.dtype)  # live microbatch on this stage
        outputs = jnp.zeros_like(xs)

        def tick(t, carry):
            state, outputs = carry
            # stage 0 injects microbatch t (when available)
            inject = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, M - 1), axis=0, keepdims=False
            )
            state = jnp.where((idx == 0) & (t < M), inject, state)
            live = (t - idx >= 0) & (t - idx < M)
            out = stage_fn(stage_params, state)
            state = jnp.where(live, out, state)
            # last stage writes its finished microbatch t - (P-1)
            done_slot = t - (n_stages - 1)
            outputs = jax.lax.cond(
                (idx == n_stages - 1) & (done_slot >= 0),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, state, jnp.clip(done_slot, 0, M - 1), axis=0
                ),
                lambda o: o,
                outputs,
            )
            # shift activations to the next stage
            state = jax.lax.ppermute(
                state, stage_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)],
            )
            # ppermute feeds stage i+1 with stage i's output; stage 0's inbox is
            # garbage from the wrap-around — it re-injects anyway.
            return state, outputs

        state, outputs = jax.lax.fori_loop(0, M + n_stages - 1, tick, (state, outputs))
        # only the last stage holds real outputs; broadcast to all stages via psum
        # after masking others to zero so every shard returns the same value.
        outputs = jnp.where(idx == n_stages - 1, outputs, jnp.zeros_like(outputs))
        outputs = jax.lax.psum(outputs, stage_axis)
        return outputs

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
        check_vma=False,
    )(params, x)


def make_pp_train_step(stage_fn, loss_fn, mesh, stage_axis: str = "stage"):
    """Toy end-to-end PP train step for the tests: forward via pipelined_apply,
    loss on the full output, grads via jax.grad through the shard_map."""

    def step(params, x, y, lr):
        def objective(p):
            out = pipelined_apply(stage_fn, p, x, mesh, stage_axis)
            return loss_fn(out, y)

        loss, grads = jax.value_and_grad(objective)(params)
        new_params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return new_params, loss

    return step
