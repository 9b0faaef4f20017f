"""Model FLOPs per token of one training step of a Mamba2 model: the
work of forward and backward passes, counted from the shapes, with
recomputation (remat) left out.

Matmuls: 6 per parameter of the input projections (z, x, B, C, dt),
the output projection and the tied head. The scan at its least work,
the recurrence: per token and head, the state update dt B (x) x and
the read-out C . h take 2 N P FLOPs each, forward; three times that
with the backward pass. The depthwise conv: 2 d_conv FLOPs per channel
of x, B and C, forward; also times three.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    d = cfg["d_model"]
    di = cfg["expand"] * d
    n = cfg["d_state"]
    heads = di // cfg["headdim"]
    per_layer = d * (2 * di + 2 * n + heads) + di * d
    return cfg["n_layer"] * per_layer + d * cfg["vocab_size"]


def flops_per_token(cfg: dict, seq: int) -> float:
    di = cfg["expand"] * cfg["d_model"]
    heads = di // cfg["headdim"]
    scan = 3 * 4 * heads * cfg["d_state"] * cfg["headdim"]
    conv = 3 * 2 * cfg["d_conv"] * (di + 2 * cfg["d_state"])
    return 6.0 * matmul_params(cfg) + cfg["n_layer"] * (scan + conv)
