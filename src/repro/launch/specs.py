"""Abstract input/step construction shared by the dry-run and the real
launchers: ``input_specs`` (ShapeDtypeStruct stand-ins for every model input)
and ``build_step`` (the jitted step with in/out shardings for a given cell).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.models import (
    ModelConfig,
    SHAPES,
    abstract_params,
    cache_logical,
    init_cache,
    params_logical,
)
from repro.models.sharding import (
    SERVE_RULES,
    TRAIN_RULES,
    ShardingRules,
    logical_to_sharding,
    tree_shardings,
    wrap_with_sharding_ctx,
)
from repro.serve import make_decode_step, make_prefill_step
from repro.train.optimizer import Optimizer
from repro.train.train_loop import (
    TrainConfig,
    _opt_shardings,
    batch_shardings,
    make_optimizer_for,
    make_train_step,
)

__all__ = ["input_specs", "build_step", "Cell"]


def _batch_abstract(cfg: ModelConfig, batch: int, seq: int) -> dict:
    i32 = jnp.int32
    if cfg.modality == "audio":
        return {
            "tokens": jax.ShapeDtypeStruct((batch, cfg.num_codebooks, seq), i32),
            "labels": jax.ShapeDtypeStruct((batch, cfg.num_codebooks, seq), i32),
        }
    if cfg.modality == "vlm":
        return {
            "tokens": jax.ShapeDtypeStruct((batch, seq - cfg.img_tokens), i32),
            "image_embeds": jax.ShapeDtypeStruct(
                (batch, cfg.img_tokens, cfg.d_model), jnp.bfloat16
            ),
            "labels": jax.ShapeDtypeStruct((batch, seq), i32),
        }
    return {
        "tokens": jax.ShapeDtypeStruct((batch, seq), i32),
        "labels": jax.ShapeDtypeStruct((batch, seq), i32),
    }


def input_specs(cfg: ModelConfig, shape_name: str) -> dict:
    """ShapeDtypeStruct stand-ins for every input of the cell's step function
    (weak-type-correct, shardable, no device allocation)."""
    shp = SHAPES[shape_name]
    if shp.kind == "train":
        return _batch_abstract(cfg, shp.global_batch, shp.seq_len)
    if shp.kind == "prefill":
        return _batch_abstract(cfg, shp.global_batch, shp.seq_len)
    # decode: one new token against a seq_len cache
    i32 = jnp.int32
    if cfg.modality == "audio":
        toks = jax.ShapeDtypeStruct((shp.global_batch, cfg.num_codebooks, 1), i32)
    else:
        toks = jax.ShapeDtypeStruct((shp.global_batch, 1), i32)
    return {"tokens": toks}


@dataclasses.dataclass
class Cell:
    """One (arch x shape x mesh) dry-run unit: a step fn + fully-specified
    abstract args + shardings, ready to ``jit(...).lower(...)``."""

    name: str
    step: Callable
    args: tuple
    in_shardings: tuple
    donate: tuple = ()


def build_step(cfg: ModelConfig, shape_name: str, mesh, tcfg: TrainConfig | None = None) -> Cell:
    shp = SHAPES[shape_name]
    aps = abstract_params(cfg)
    p_logical = params_logical(cfg)

    if shp.kind == "train":
        rules = TRAIN_RULES
        p_sh = tree_shardings(aps, p_logical, mesh, rules)
        opt = make_optimizer_for(cfg, tcfg or TrainConfig())
        opt_abs = jax.eval_shape(opt.init, aps)
        o_sh = _opt_shardings(opt_abs, p_sh)
        batch_abs = input_specs(cfg, shape_name)
        b_sh = batch_shardings(batch_abs, mesh, rules)
        step = wrap_with_sharding_ctx(
            make_train_step(cfg, opt, cfg.train_microbatch), mesh, rules
        )
        scalar = jax.ShapeDtypeStruct((), jnp.int32)
        scalar_sh = NamedSharding(mesh, PartitionSpec())
        return Cell(
            name=f"{cfg.name}:{shape_name}",
            step=step,
            args=(aps, opt_abs, scalar, batch_abs),
            in_shardings=(p_sh, o_sh, scalar_sh, b_sh),
            donate=(0, 1),
        )

    rules = SERVE_RULES
    if cfg.serve_fsdp:
        rules = ShardingRules({**SERVE_RULES.rules, "fsdp_embed": ("pod", "data")})
    # serving runs on bf16 weights (f32 masters stay in the checkpoint)
    sdt = jnp.dtype(cfg.serve_param_dtype)
    aps = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, sdt), aps)
    p_sh = tree_shardings(aps, p_logical, mesh, rules)
    cache_abs = jax.eval_shape(
        lambda: init_cache(cfg, shp.global_batch, shp.seq_len, jnp.bfloat16)
    )
    c_logical = cache_logical(cache_abs)
    c_sh = tree_shardings(cache_abs, c_logical, mesh, rules)

    if shp.kind == "prefill":
        batch_abs = input_specs(cfg, shape_name)
        b_sh = batch_shardings(batch_abs, mesh, rules)
        step = wrap_with_sharding_ctx(make_prefill_step(cfg), mesh, rules)
        return Cell(
            name=f"{cfg.name}:{shape_name}",
            step=step,
            args=(aps, batch_abs, cache_abs),
            in_shardings=(p_sh, b_sh, c_sh),
            donate=(2,),
        )

    # decode
    tok_abs = input_specs(cfg, shape_name)["tokens"]
    tok_logical = ("batch", None, None)[: len(tok_abs.shape)]
    t_sh = logical_to_sharding(tok_logical, tok_abs.shape, mesh, rules)
    idx_abs = jax.ShapeDtypeStruct((), jnp.int32)
    idx_sh = NamedSharding(mesh, PartitionSpec())
    step = wrap_with_sharding_ctx(make_decode_step(cfg), mesh, rules)
    return Cell(
        name=f"{cfg.name}:{shape_name}",
        step=step,
        args=(aps, tok_abs, cache_abs, idx_abs),
        in_shardings=(p_sh, t_sh, c_sh, idx_sh),
        donate=(2,),
    )
