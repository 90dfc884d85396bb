"""Plain float32 reference of the SmolLM-135M decoder and its first AdamW steps.

Written from the published architecture (hf:HuggingFaceTB/SmolLM-135M, a
Llama-style decoder) in straightforward ``jax.numpy``, with no kernels,
chunking of attention or mixed precision, and nothing imported from the
system under test.  It is what the smollm cells' ``correct`` compares with:
the losses of a trial's first steps, the first gradient as the optimizer
receives it, and the change of the parameters after those steps.

- Layers: pre-norm RMSNorm, grouped-query attention with rotary embeddings
  (half-split rotation), causal softmax, SwiGLU MLP; a final RMSNorm; the
  output head is the transposed embedding (tied).  Each RMSNorm gain is kept
  as an offset from one, ``x * rsqrt(mean(x^2) + eps) * (1 + g)``, so that a
  gain initialised at zero is the published weight of one.
- Loss: mean token cross-entropy.
- Optimizer: AdamW (decoupled weight decay on every parameter) after
  clipping the gradient to global norm ``clip_norm``; learning rate warmed
  up linearly over ``warmup`` steps (the first step already trains at
  ``lr / warmup``), then a cosine decay to ``0.1 * lr`` at ``total`` steps.
- Weights: each parameter ``normal(fold_in(PRNGKey(seed), h(path))) * std``
  with ``h`` the 31-bit FNV-1a hash of the parameter's path string, zeros
  for the norm gains; ``std`` is 0.02 for the embedding and ``1/sqrt(fan
  in)`` for the projections.  This is how the tuned trainer draws a trial's
  weights from its seed, so the reference can make the same weights without
  taking any from the program.
- Precision: every matrix product runs at ``highest`` precision (on a TPU a
  float32 product is otherwise computed in bfloat16 passes).  With
  ``round_to`` set, both operands of every product are first rounded to that
  type: the control, a reference computed in a lower precision than the
  configuration states.

Memory: the layers are scanned with rematerialisation, and the loss head is
computed one batch row at a time, so the full-width model at 8 x 2048 fits
one 16 GB chip.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32


def dims(cfg: dict) -> dict:
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return {
        "d": d, "ff": cfg["intermediate_size"], "L": cfg["num_hidden_layers"],
        "H": heads, "KV": cfg["num_key_value_heads"], "Dh": cfg.get("head_dim", d // heads),
        "V": cfg["vocab_size"], "theta": float(cfg["rope_theta"]), "eps": float(cfg["rms_norm_eps"]),
    }


def _fnv(path: str) -> int:
    h = 2166136261
    for ch in path:
        h = ((h ^ ord(ch)) * 16777619) % (2**31)
    return h


def param_shapes(cfg: dict) -> dict:
    """``path -> (shape, std)``; ``std`` ``None`` means zeros."""
    k = dims(cfg)
    d, ff, L, H, KV, Dh, V = k["d"], k["ff"], k["L"], k["H"], k["KV"], k["Dh"], k["V"]
    s = "['stack']['0']"
    return {
        "['embed']": ((V, d), 0.02),
        "['final_norm']": ((d,), None),
        f"{s}['attn']['wk']": ((L, d, KV, Dh), 1 / math.sqrt(d)),
        f"{s}['attn']['wo']": ((L, H, Dh, d), 1 / math.sqrt(H * Dh)),
        f"{s}['attn']['wq']": ((L, d, H, Dh), 1 / math.sqrt(d)),
        f"{s}['attn']['wv']": ((L, d, KV, Dh), 1 / math.sqrt(d)),
        f"{s}['ln1']": ((L, d), None),
        f"{s}['ln2']": ((L, d), None),
        f"{s}['w1']": ((L, d, ff), 1 / math.sqrt(d)),
        f"{s}['w2']": ((L, ff, d), 1 / math.sqrt(ff)),
        f"{s}['w3']": ((L, d, ff), 1 / math.sqrt(d)),
    }


def init_params(cfg: dict, seed) -> dict:
    """Flat ``path -> array`` of the weights drawn from ``seed``."""
    key = jax.random.PRNGKey(seed)
    out = {}
    for path, (shape, std) in param_shapes(cfg).items():
        if std is None:
            out[path] = jnp.zeros(shape, F32)
        else:
            out[path] = jax.random.normal(jax.random.fold_in(key, _fnv(path)), shape, F32) * std
    return out


def _mm(eq: str, a, b, round_to):
    if round_to is not None:
        a = a.astype(round_to).astype(F32)
        b = b.astype(round_to).astype(F32)
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + g)


def _rope(x, positions, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = positions.astype(F32)[:, None] * freqs  # [S, half]
    sin, cos = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def loss(params: dict, tokens, labels, cfg: dict, round_to=None):
    """Mean token cross-entropy of ``labels`` given ``tokens`` ([B, S] int)."""
    k = dims(cfg)
    mm = partial(_mm, round_to=round_to)
    S = tokens.shape[1]
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]  # [q, k]
    s = "['stack']['0']"
    names = ("['attn']['wq']", "['attn']['wk']", "['attn']['wv']", "['attn']['wo']",
             "['ln1']", "['ln2']", "['w1']", "['w2']", "['w3']")
    stacked = tuple(params[s + n] for n in names)
    group = k["H"] // k["KV"]

    @jax.checkpoint
    def layer(x, p):
        wq, wk, wv, wo, ln1, ln2, w1, w2, w3 = p
        h = _norm(x, ln1, k["eps"])
        q = _rope(mm("bsd,dhk->bshk", h, wq), pos, k["theta"])
        kk = _rope(mm("bsd,dhk->bshk", h, wk), pos, k["theta"])
        v = mm("bsd,dhk->bshk", h, wv)
        kk = jnp.repeat(kk, group, axis=2)  # query head i reads kv head i // group
        v = jnp.repeat(v, group, axis=2)
        sc = mm("bqhd,bkhd->bhqk", q, kk) / math.sqrt(k["Dh"])
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        o = mm("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v)
        x = x + mm("bshk,hkd->bsd", o, wo)
        h = _norm(x, ln2, k["eps"])
        a, b = mm("bsd,df->bsf", h, w1), mm("bsd,df->bsf", h, w3)
        return x + mm("bsf,fd->bsd", jax.nn.silu(a) * b, w2), None

    emb = params["['embed']"]
    x = jnp.take(emb, tokens, axis=0)
    x, _ = jax.lax.scan(layer, x, stacked)
    x = _norm(x, params["['final_norm']"], k["eps"])

    @jax.checkpoint
    def row_nll(args):
        xr, lr = args
        logits = mm("sd,vd->sv", xr, emb)
        ll = jnp.take_along_axis(logits, lr[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - ll)

    return jnp.sum(jax.lax.map(row_nll, (x, labels))) / labels.size


def learning_rate(step, lr, warmup, total, floor: float = 0.1):
    step = jnp.asarray(step, F32)
    warm = lr * jnp.minimum(1.0, (step + 1.0) / jnp.maximum(warmup, 1.0))
    frac = jnp.clip((step - warmup) / jnp.maximum(total - warmup, 1.0), 0.0, 1.0)
    cos = lr * (floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
    return jnp.where(step < warmup, warm, cos)


def _leaf_norms(tree: dict):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


@partial(jax.jit, static_argnames=("cfg_items", "n_steps", "round_to", "half_batch"))
def _first_steps(seed, hyper, tokens, labels, *, cfg_items, n_steps, round_to, half_batch):
    cfg = dict(cfg_items)
    lr, warmup, wd, total, b1, b2, eps, clip = (hyper[i] for i in range(8))
    p0 = init_params(cfg, seed)
    params = p0
    m = {k: jnp.zeros_like(v) for k, v in p0.items()}
    v2 = {k: jnp.zeros_like(v) for k, v in p0.items()}
    losses, first_grad = [], None
    for t in range(n_steps):
        tok, lab = tokens[t], labels[t]
        if half_batch:  # a planted fault: half of the batch left out
            tok, lab = tok[: tok.shape[0] // 2], lab[: lab.shape[0] // 2]
        val, g = jax.value_and_grad(loss)(params, tok, lab, cfg, round_to)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
        scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
        g = {k: x * scale for k, x in g.items()}
        if t == 0:
            first_grad = _leaf_norms(g)
        c1, c2 = 1.0 - b1 ** (t + 1.0), 1.0 - b2 ** (t + 1.0)
        rate = learning_rate(t, lr, warmup, total)
        m = {k: b1 * m[k] + (1 - b1) * g[k] for k in g}
        v2 = {k: b2 * v2[k] + (1 - b2) * g[k] * g[k] for k in g}
        params = {
            k: params[k] - rate * ((m[k] / c1) / (jnp.sqrt(v2[k] / c2) + eps) + wd * params[k])
            for k in params
        }
        losses.append(val)
    change = _leaf_norms({k: params[k] - p0[k] for k in params})
    return jnp.stack(losses), first_grad, change


def first_steps(cfg: dict, seed: int, hyper: dict, tokens, labels,
                round_to=None, half_batch: bool = False) -> dict:
    """The reference's first ``len(tokens)`` AdamW steps from the weights of
    ``seed``: each step's loss, the per-leaf norms of the first (clipped)
    gradient, and of the change of each leaf after the last step.

    ``hyper``: ``lr``, ``warmup``, ``weight_decay``, ``total_steps``, ``b1``,
    ``b2``, ``eps``, ``clip_norm``.  ``tokens``/``labels``: [steps, B, S]."""
    keys = ("lr", "warmup", "weight_decay", "total_steps", "b1", "b2", "eps", "clip_norm")
    h = jnp.asarray([float(hyper[k]) for k in keys], F32)
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items() if k in _ARCH_KEYS))
    with jax.default_matmul_precision("highest"):
        losses, grad, change = _first_steps(
            jnp.uint32(seed), h, jnp.asarray(tokens), jnp.asarray(labels),
            cfg_items=cfg_items, n_steps=len(tokens), round_to=round_to, half_batch=half_batch,
        )
    return {
        "losses": [float(x) for x in losses],
        "grad": {k: float(x) for k, x in grad.items()},
        "change": {k: float(x) for k, x in change.items()},
    }


_ARCH_KEYS = (
    "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "vocab_size", "rope_theta", "rms_norm_eps",
)
