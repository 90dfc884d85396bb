"""Optimizer settings as operands: the schedule and every optimizer's update
read them from float32 scalars in the state, and give what the same
settings give as constants (the schedule as it was compiled in before, each
update against a float64 reference) within float32 round-off."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import make_optimizer, warmup_cosine

TOTAL = 8
STEPS = TOTAL + 4  # past the end of the schedule


def _constant_warmup_cosine(peak_lr: float, warmup: int, total: int, floor: float = 0.1):
    """The schedule as it was with its settings as Python constants."""

    def schedule(step):
        step = jnp.asarray(step, jnp.float32)
        warm = peak_lr * jnp.minimum(1.0, (step + 1.0) / max(warmup, 1))
        frac = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = peak_lr * (floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
        return jnp.where(step < warmup, warm, cos)

    return schedule


@pytest.mark.parametrize("warmup", [0, 1, 3, TOTAL + 5])
def test_schedule_with_operand_settings_matches_constants(warmup):
    steps = jnp.arange(STEPS, dtype=jnp.int32)
    const = jax.jit(jax.vmap(_constant_warmup_cosine(3e-3, warmup, TOTAL)))(steps)
    operand = jax.jit(lambda lr, w, t: jax.vmap(warmup_cosine(lr, w, t))(steps))(
        np.float32(3e-3), np.float32(warmup), np.float32(TOTAL)
    )
    np.testing.assert_allclose(np.asarray(operand), np.asarray(const), rtol=1e-6, atol=0)
    assert float(operand[0]) > 0  # the first step trains


def _params():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    return {"w": jax.random.normal(k1, (4, 3)), "b": jax.random.normal(k2, (5,))}


def _grads(i: int, params):
    keys = jax.random.split(jax.random.PRNGKey(100 + i), 2)
    return {k: 3.0 * jax.random.normal(key, params[k].shape) for k, key in zip(sorted(params), keys)}


SETTINGS = {
    "adamw": dict(b1=0.85, b2=0.97, weight_decay=0.05),
    "adafactor": dict(weight_decay=0.02),
    "sgd": dict(),
}


def _reference_update(kind, s, grads, state, params, step):
    """One update in float64 numpy, its settings ``s`` constants."""
    gnorm = np.sqrt(sum(np.sum(g * g) for g in grads.values()))
    scale = min(1.0, s["clip_norm"] / max(gnorm, 1e-9))
    lr = np.asarray(_constant_warmup_cosine(s["lr"], s["warmup_steps"], s["total_steps"])(step),
                    np.float64)
    t = step + 1.0
    new_p, new_s = {}, {}
    for k, p in params.items():
        g = grads[k] * scale
        if kind == "adamw":
            m = s["b1"] * state[k][0] + (1 - s["b1"]) * g
            v = s["b2"] * state[k][1] + (1 - s["b2"]) * g * g
            u = (m / (1 - s["b1"] ** t)) / (np.sqrt(v / (1 - s["b2"] ** t)) + 1e-8)
            new_p[k], new_s[k] = p - lr * (u + s["weight_decay"] * p), (m, v)
        elif kind == "sgd":
            mu = 0.9 * state[k] + g
            new_p[k], new_s[k] = p - lr * mu, mu
        else:
            eps, beta = 1e-30, 1.0 - t**-0.8
            g2 = g * g + eps
            if p.ndim == 2:
                vr = beta * state[k][0] + (1 - beta) * g2.mean(-1)
                vc = beta * state[k][1] + (1 - beta) * g2.mean(-2)
                u = g / np.sqrt((vr / max(vr.mean(), eps))[:, None] * vc[None, :] + eps)
                new_s[k] = (vr, vc)
            else:
                v = beta * state[k] + (1 - beta) * g2
                u = g / np.sqrt(v + eps)
                new_s[k] = v
            u = u / max(1.0, np.sqrt(np.mean(u * u) + eps))
            new_p[k] = p - lr * (u + s["weight_decay"] * p)
    return new_p, new_s


def _reference_state(kind, params):
    z = {k: np.zeros(p.shape) for k, p in params.items()}
    if kind == "adamw":
        return {k: (v, v) for k, v in z.items()}
    if kind == "adafactor":
        return {k: (np.zeros(p.shape[0]), np.zeros(p.shape[1])) if p.ndim == 2 else z[k]
                for k, p in params.items()}
    return z


@pytest.mark.parametrize("warmup", [0, 1, TOTAL + 5])
@pytest.mark.parametrize("kind", sorted(SETTINGS))
def test_update_with_operand_settings_matches_constants(kind, warmup):
    """Each update, its settings operands of one compiled step, against the
    same settings as constants (float64 numpy) over steps 0..N."""
    opt = make_optimizer(kind, lr=2e-2, warmup_steps=warmup, total_steps=TOTAL, clip_norm=1.5,
                         **SETTINGS[kind])
    constants = {k: v.item() for k, v in opt.hyper.items()}
    update = jax.jit(opt.update)
    params = _params()
    state = opt.init(params)
    assert {k: v.dtype for k, v in state["hyper"].items()} == dict.fromkeys(opt.hyper, jnp.float32)
    ref_p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    ref_s = _reference_state(kind, ref_p)
    for i in range(STEPS):
        grads = _grads(i, params)
        params, state, metrics = update(grads, state, params, jnp.int32(i))
        ref_p, ref_s = _reference_update(
            kind, constants, {k: np.asarray(g, np.float64) for k, g in grads.items()},
            ref_s, ref_p, i,
        )
        for k in ref_p:
            np.testing.assert_allclose(np.asarray(params[k]), ref_p[k], rtol=1e-5, atol=1e-6)
        # the settings come back unchanged
        assert {k: v.item() for k, v in state["hyper"].items()} == constants
    assert update._cache_size() == 1  # one program for every step
