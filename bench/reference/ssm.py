"""Plain reference of a Mamba2 language model: its mean next-token
cross-entropy, in float32.

Layer equations (arXiv:2405.21060, Mamba2 with one group):
  h = n1(x); z, u, B, C, dt_raw = h Wz, h Wx, h Wb, h Wc, h Wdt
  [u, B, C] <- silu(causal depthwise conv of width d_conv, with bias)
  dt = softplus(dt_raw + dt_bias); A = -exp(A_log)     (one per head)
  y_t = sum_{s<=t} (C_t . B_s) exp(A sum_{s<r<=t} dt_r) dt_s u_s + D u_t
  x <- x + Wout (RMSNorm(y * silu(z)) * norm_scale)
with tied embeddings: logits = nf(x) E^T. The scan is written in its
quadratic (attention-like) form over the whole sequence, which is the
recurrence itself with no chunking. Norm eps 1e-6 and a float32 residual
as listed in the config file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import cross_entropy, rms_norm

EPS = 1e-6


def _layer(x, p, cfg, dot):
    Bsz, S, _ = x.shape
    N = cfg["d_state"]
    P = cfg["headdim"]
    di = cfg["expand"] * cfg["d_model"]
    H = di // P
    m = p["mixer"]
    h = rms_norm(x, p["norm1"]["scale"], EPS)
    z = dot("bsd,de->bse", h, m["in_z"]["w"])
    u = dot("bsd,de->bse", h, m["in_x"]["w"])
    b = dot("bsd,dn->bsn", h, m["in_b"]["w"])
    c = dot("bsd,dn->bsn", h, m["in_c"]["w"])
    dt_raw = dot("bsd,dh->bsh", h, m["in_dt"]["w"])
    conv_in = jnp.concatenate([u, b, c], axis=-1)
    W = m["conv_w"].shape[0]
    padded = jnp.pad(conv_in, ((0, 0), (W - 1, 0), (0, 0)))
    conv = m["conv_b"] + sum(
        padded[:, i:i + S, :] * m["conv_w"][i] for i in range(W))
    conv = jax.nn.silu(conv)
    u, b, c = conv[..., :di], conv[..., di:di + N], conv[..., di + N:]
    dt = jax.nn.softplus(dt_raw + m["dt_bias"])               # (B, S, H)
    A = -jnp.exp(m["A_log"])                                   # (H,)
    cum = jnp.cumsum(dt * A, axis=1)                           # (B, S, H)
    seg = cum[:, :, None, :] - cum[:, None, :, :]              # (B, t, s, H)
    causal = np.tril(np.ones((S, S), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = dot("btn,bsn->bts", c, b)
    mat = cb[..., None] * decay * dt[:, None, :, :]            # (B, t, s, H)
    uh = u.reshape(Bsz, S, H, P)
    y = dot("btsh,bshp->bthp", mat, uh) + uh * m["D"][:, None]
    y = y.reshape(Bsz, S, di) * jax.nn.silu(z)
    y = rms_norm(y, m["norm_scale"], EPS)
    return x + dot("bse,ed->bsd", y, m["out"]["w"])


def loss(params, tokens, labels, cfg, dot):
    """Mean cross-entropy of one node's batch; tokens, labels (B, S)."""
    V = cfg["vocab_size"]
    table = params["embed"]["table"]
    x = jnp.take(table, tokens, axis=0)

    def body(x, p):
        return jax.checkpoint(lambda x, p: _layer(x, p, cfg, dot))(x, p), None

    x, _ = jax.lax.scan(body, x, params["blocks_0"])
    x = rms_norm(x, params["final_norm"]["scale"], EPS)
    logits = dot("bsd,vd->bsv", x, table[:V])
    return cross_entropy(logits, labels)
