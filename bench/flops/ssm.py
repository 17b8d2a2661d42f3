"""Model FLOPs per trained token of the RWKV-6 family.

6 x the matmul parameters (the five time-mix projections, the decay LoRA,
the three channel-mix projections, and the head) plus the recurrence: per
token and layer 2 d n for the state update k vᵀ and 2 d n for the readout
rᵀ S forward, three times that with the backward pass (n = head size).
The embedding lookup is a gather and is left out, and so is recomputation.
"""
from __future__ import annotations

LORA_RANK = 64


def matmul_params(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    v, L = cfg["vocab_size"], cfg["num_hidden_layers"]
    per_layer = 5 * d * d + 2 * d * LORA_RANK + 2 * d * f + d * d
    return L * per_layer + d * v


def flops_per_token(cfg: dict, seq: int) -> float:
    d, n, L = cfg["hidden_size"], cfg["head_size"], cfg["num_hidden_layers"]
    return 6.0 * matmul_params(cfg) + 3 * 4.0 * L * d * n
