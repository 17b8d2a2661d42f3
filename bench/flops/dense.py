"""Model FLOPs per trained token of the dense decoder family.

6 x the matmul parameters (forward 2, backward 4) plus 12 L d S for the
attention scores and their weighted sum over S positions.  The embedding
lookup is a gather and is left out, and so is recomputation.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // h
    f, v, L = cfg["intermediate_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    per_layer = d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f
    return L * per_layer + d * v


def flops_per_token(cfg: dict, seq: int) -> float:
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    return 6.0 * matmul_params(cfg) + 12.0 * L * d * seq
