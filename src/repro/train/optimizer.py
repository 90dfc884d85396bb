"""Optimizers in pure JAX: AdamW, Adafactor (factored second moments — the
235B-config choice), SGD+momentum; global-norm clipping; a warmup+cosine
schedule.

Optimizer state is a pytree parallel to params, so GSPMD shards it exactly
like the parameters (ZeRO-style for free when params are FSDP-sharded).
Beside it, under ``"hyper"``, the state holds the optimizer's settings
(learning rate, schedule, decay, betas, clipping) as float32 scalars that the
update reads, as optax's ``inject_hyperparams`` does: a compiled step takes
them as operands, so trials that differ only in their settings share it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "Optimizer",
    "adamw",
    "adafactor",
    "sgd",
    "make_optimizer",
    "warmup_cosine",
    "global_norm",
    "clip_by_global_norm",
]


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree)]
    return jnp.sqrt(sum(leaves))


def clip_by_global_norm(tree, max_norm: float):
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), tree), norm


def warmup_cosine(peak_lr, warmup, total, floor: float = 0.1) -> Callable:
    """Linear warm-up to ``peak_lr`` over ``warmup`` steps, then a cosine to
    ``floor * peak_lr`` at ``total``.  The settings may be numbers or float32
    scalars (operands of a compiled step)."""

    def schedule(step):
        step = jnp.asarray(step, jnp.float32)
        # (step+1)/warmup so the very first step trains (lr > 0 at step 0)
        warm = peak_lr * jnp.minimum(1.0, (step + 1.0) / jnp.maximum(warmup, 1))
        frac = jnp.clip((step - warmup) / jnp.maximum(total - warmup, 1), 0.0, 1.0)
        cos = peak_lr * (floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
        return jnp.where(step < warmup, warm, cos)

    return schedule


@dataclasses.dataclass(frozen=True)
class Optimizer:
    """An optimizer's programs, which depend on ``key`` alone, and its
    settings ``hyper``.

    ``init(params)`` returns the state: the moments, and the settings as
    float32 scalars under ``"hyper"``; ``update(grads, state, params, step)``
    reads them from there and returns them unchanged.  Optimizers with equal
    ``key`` (the kind and the options that shape the program) share every
    compiled program."""

    moments: Callable[[Any], dict]
    update: Callable[[Any, Any, Any, jax.Array], tuple]  # (grads, state, params, step)
    hyper: dict
    key: tuple

    def init(self, params, hyper: dict | None = None) -> dict:
        """The state for ``params``, holding ``hyper`` (by default this
        optimizer's own settings)."""
        hyper = self.hyper if hyper is None else hyper
        return {**self.moments(params),
                "hyper": {k: jnp.asarray(v, jnp.float32) for k, v in hyper.items()}}


def _float32(**settings) -> dict:
    return {k: np.float32(v) for k, v in settings.items()}


def _schedule(h: dict) -> Callable:
    return warmup_cosine(h["lr"], h["warmup_steps"], h["total_steps"])


def adamw(
    lr: float,
    warmup_steps: int,
    total_steps: int,
    b1: float = 0.9,
    b2: float = 0.95,
    eps: float = 1e-8,
    weight_decay: float = 0.1,
    clip_norm: float = 1.0,
    state_dtype=jnp.float32,
) -> Optimizer:
    def moments(params):
        zeros = lambda p: jnp.zeros(p.shape, state_dtype)
        return {
            "m": jax.tree.map(zeros, params),
            "v": jax.tree.map(zeros, params),
        }

    def update(grads, state, params, step):
        h = state["hyper"]
        b1, b2, weight_decay = h["b1"], h["b2"], h["weight_decay"]
        grads, gnorm = clip_by_global_norm(grads, h["clip_norm"])
        t = step.astype(jnp.float32) + 1.0
        c1 = 1.0 - b1**t
        c2 = 1.0 - b2**t
        lr = _schedule(h)(step)

        def upd(g, m, v, p):
            g32 = g.astype(jnp.float32)
            m_new = b1 * m.astype(jnp.float32) + (1 - b1) * g32
            v_new = b2 * v.astype(jnp.float32) + (1 - b2) * g32 * g32
            mhat = m_new / c1
            vhat = v_new / c2
            step_ = mhat / (jnp.sqrt(vhat) + eps) + weight_decay * p.astype(jnp.float32)
            p_new = p.astype(jnp.float32) - lr * step_
            return p_new.astype(p.dtype), m_new.astype(state_dtype), v_new.astype(state_dtype)

        out = jax.tree.map(upd, grads, state["m"], state["v"], params)
        p_new = jax.tree.map(lambda o: o[0], out, is_leaf=lambda x: isinstance(x, tuple))
        m_new = jax.tree.map(lambda o: o[1], out, is_leaf=lambda x: isinstance(x, tuple))
        v_new = jax.tree.map(lambda o: o[2], out, is_leaf=lambda x: isinstance(x, tuple))
        return p_new, {"m": m_new, "v": v_new, "hyper": h}, {"grad_norm": gnorm, "lr": lr}

    hyper = _float32(lr=lr, warmup_steps=warmup_steps, total_steps=total_steps, b1=b1, b2=b2,
                     weight_decay=weight_decay, clip_norm=clip_norm)
    return Optimizer(moments, update, hyper, ("adamw", eps, jnp.dtype(state_dtype).name))


def adafactor(
    lr: float,
    warmup_steps: int,
    total_steps: int,
    decay: float = 0.99,
    eps: float = 1e-30,
    clip_threshold: float = 1.0,
    weight_decay: float = 0.0,
    clip_norm: float = 1.0,
) -> Optimizer:
    """Factored second-moment optimizer (Shazeer & Stern 2018), beta1=0.

    For a [r, c] matrix the state is r + c floats instead of r*c — this is
    what lets qwen3-moe-235b train on one 256-chip pod (see DESIGN.md)."""

    def factored(p) -> bool:
        return p.ndim >= 2 and p.shape[-1] > 1 and p.shape[-2] > 1

    def moments(params):
        def one(p):
            if factored(p):
                return {
                    "vr": jnp.zeros(p.shape[:-1], jnp.float32),
                    "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32),
                }
            return {"v": jnp.zeros(p.shape, jnp.float32)}

        return {"v": jax.tree.map(one, params)}

    def update(grads, state, params, step):
        h = state["hyper"]
        weight_decay = h["weight_decay"]
        grads, gnorm = clip_by_global_norm(grads, h["clip_norm"])
        lr = _schedule(h)(step)
        t = step.astype(jnp.float32) + 1.0
        beta2t = 1.0 - jnp.power(t, -0.8)  # Adafactor's decay schedule

        def upd(g, st, p):
            g32 = g.astype(jnp.float32)
            g2 = g32 * g32 + eps
            if factored(p):
                vr = beta2t * st["vr"] + (1 - beta2t) * g2.mean(axis=-1)
                vc = beta2t * st["vc"] + (1 - beta2t) * g2.mean(axis=-2)
                denom = jnp.maximum(vr.mean(axis=-1, keepdims=True), eps)
                u = g32 / jnp.sqrt(
                    (vr / denom)[..., None] * vc[..., None, :] + eps
                )
                new_st = {"vr": vr, "vc": vc}
            else:
                v = beta2t * st["v"] + (1 - beta2t) * g2
                u = g32 / jnp.sqrt(v + eps)
                new_st = {"v": v}
            # update clipping by RMS
            rms = jnp.sqrt(jnp.mean(u * u) + eps)
            u = u / jnp.maximum(1.0, rms / clip_threshold)
            p_new = p.astype(jnp.float32) - lr * (u + weight_decay * p.astype(jnp.float32))
            return p_new.astype(p.dtype), new_st

        # note: state["v"] carries an extra {vr,vc}/{v} dict *below* each param
        # leaf; tree.map flattens the later trees only up to `grads` leaves, so
        # `st` arrives as that dict.
        out = jax.tree.map(upd, grads, state["v"], params)
        is_pair = lambda x: isinstance(x, tuple)
        p_new = jax.tree.map(lambda o: o[0], out, is_leaf=is_pair)
        v_new = jax.tree.map(lambda o: o[1], out, is_leaf=is_pair)
        return p_new, {"v": v_new, "hyper": h}, {"grad_norm": gnorm, "lr": lr}

    hyper = _float32(lr=lr, warmup_steps=warmup_steps, total_steps=total_steps,
                     weight_decay=weight_decay, clip_norm=clip_norm)
    return Optimizer(moments, update, hyper, ("adafactor", decay, eps, clip_threshold))


def sgd(
    lr: float,
    warmup_steps: int,
    total_steps: int,
    momentum: float = 0.9,
    clip_norm: float = 1.0,
) -> Optimizer:
    def moments(params):
        return {"mu": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)}

    def update(grads, state, params, step):
        h = state["hyper"]
        grads, gnorm = clip_by_global_norm(grads, h["clip_norm"])
        lr = _schedule(h)(step)

        def upd(g, mu, p):
            mu_new = momentum * mu + g.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * mu_new).astype(p.dtype), mu_new

        out = jax.tree.map(upd, grads, state["mu"], params)
        is_pair = lambda x: isinstance(x, tuple)
        return (
            jax.tree.map(lambda o: o[0], out, is_leaf=is_pair),
            {"mu": jax.tree.map(lambda o: o[1], out, is_leaf=is_pair), "hyper": h},
            {"grad_norm": gnorm, "lr": lr},
        )

    hyper = _float32(lr=lr, warmup_steps=warmup_steps, total_steps=total_steps, clip_norm=clip_norm)
    return Optimizer(moments, update, hyper, ("sgd", momentum))


def make_optimizer(name: str, **kw) -> Optimizer:
    if name == "adamw":
        return adamw(**kw)
    if name == "adafactor":
        return adafactor(**kw)
    if name == "sgd":
        return sgd(**kw)
    raise ValueError(f"unknown optimizer {name!r}")
