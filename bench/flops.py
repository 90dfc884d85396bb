"""Operations that the measured work needs, from its shapes.

These are the yardstick's own counts: ``train_mfu_pct`` divides them by the
chip's published peak.
"""

from __future__ import annotations


def decoder_matmul_params(cfg: dict) -> int:
    """Weights a token passes through in matrix products, per forward pass,
    of a decoder with grouped-query attention, a gated (SwiGLU) MLP and an
    output head over the vocabulary (the embedding lookup multiplies
    nothing)."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    head_dim = cfg.get("head_dim", d // heads)
    attn = d * heads * head_dim * 2 + d * kv * head_dim * 2  # q, o; k, v
    mlp = 3 * d * ff  # gate, up, down
    return cfg["num_hidden_layers"] * (attn + mlp) + d * cfg["vocab_size"]


def decoder_train_flops_per_token(cfg: dict, seq: int) -> float:
    """Model operations per trained token, forward and backward (3x the
    forward), causal attention counted over the keys at or before each
    query (on average ``(seq + 1) / 2``); recomputation does not count."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    head_dim = cfg.get("head_dim", d // heads)
    dense = 2 * decoder_matmul_params(cfg)
    attn = cfg["num_hidden_layers"] * 4 * heads * head_dim * (seq + 1) / 2  # q.k and p.v
    return 3 * (dense + attn)

