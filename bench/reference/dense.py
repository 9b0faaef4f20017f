"""Plain reference of a dense GQA decoder (internlm2): its mean
next-token cross-entropy, in float32.

Layer equations (arXiv:2403.17297, the Llama-style block InternLM2
publishes): x <- x + Wo attn(rope(Wq n1(x)), rope(Wk n1(x)), Wv n1(x)),
causal softmax with query head h reading key/value head h // (H / Hkv);
x <- x + W2 (silu(W1 n2(x)) * W3 n2(x)); logits = Wu nf(x), untied.
RoPE rotates the two halves of each head (theta = rope_theta). The
norms are RMSNorm with eps 1e-6, as the program computes them (the
published eps is 1e-5: the config file lists the departure).

Parameters come in the program's layout: ``embed.table``,
``unembed.w``, ``final_norm.scale`` and ``blocks_0`` holding every
layer stacked on a leading dim. Logits past ``vocab_size`` (padding
rows of the tables) are left out of the softmax.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import cross_entropy, rms_norm

EPS = 1e-6


def _rope(x, theta):
    """x: (B, S, H, D), positions 0..S-1."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, D, 2, dtype=np.float64) / D))
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    a, b = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _layer(x, p, cfg, dot):
    B, S, _ = x.shape
    H = cfg["num_attention_heads"]
    Hkv = cfg["num_key_value_heads"]
    D = cfg["head_dim"]
    h = rms_norm(x, p["norm1"]["scale"], EPS)
    a = p["mixer"]
    q = dot("bsd,de->bse", h, a["wq"]["w"]).reshape(B, S, H, D)
    k = dot("bsd,de->bse", h, a["wk"]["w"]).reshape(B, S, Hkv, D)
    v = dot("bsd,de->bse", h, a["wv"]["w"]).reshape(B, S, Hkv, D)
    q = _rope(q, cfg["rope_theta"])
    k = _rope(k, cfg["rope_theta"])
    # query head h attends with key/value head h // group
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    scores = dot("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    causal = np.tril(np.ones((S, S), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = dot("bhqk,bkhd->bqhd", probs, v).reshape(B, S, H * D)
    x = x + dot("bse,ed->bsd", o, a["wo"]["w"])
    h = rms_norm(x, p["norm2"]["scale"], EPS)
    f = p["ffn"]
    u = jax.nn.silu(dot("bsd,df->bsf", h, f["w1"]["w"]))
    u = u * dot("bsd,df->bsf", h, f["w3"]["w"])
    return x + dot("bsf,fd->bsd", u, f["w2"]["w"])


def loss(params, tokens, labels, cfg, dot):
    """Mean cross-entropy of one node's batch; tokens, labels (B, S)."""
    V = cfg["vocab_size"]
    x = jnp.take(params["embed"]["table"], tokens, axis=0)

    def body(x, p):
        return jax.checkpoint(lambda x, p: _layer(x, p, cfg, dot))(x, p), None

    x, _ = jax.lax.scan(body, x, params["blocks_0"])
    x = rms_norm(x, params["final_norm"]["scale"], EPS)
    logits = dot("bsd,dv->bsv", x, params["unembed"]["w"][:, :V])
    return cross_entropy(logits, labels)
