"""Model FLOPs per token of one training step of a dense GQA decoder:
the work of forward and backward passes, counted from the shapes, with
recomputation (remat) left out.

Matmuls: 6 per parameter of every projection and of the output head
(the embedding lookup is no matmul). Causal attention: query t scores
and mixes t + 1 keys, 2 H D (t + 1) FLOPs each for QK and PV, so the
mean token costs 2 H D (S + 1) forward and three times that with the
backward pass.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    hd = cfg["head_dim"]
    q = d * cfg["num_attention_heads"] * hd
    kv = 2 * d * cfg["num_key_value_heads"] * hd
    o = cfg["num_attention_heads"] * hd * d
    ffn = 3 * d * cfg["intermediate_size"]
    head = d * cfg["vocab_size"]
    return cfg["num_hidden_layers"] * (q + kv + o + ffn) + head


def flops_per_token(cfg: dict, seq: int) -> float:
    attn = (6 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * (seq + 1))
    return 6.0 * matmul_params(cfg) + attn
