"""Plain float32 arithmetic shared by the references: matmuls at
``highest`` precision, the scaled-float8 matmul of the control, the
SGD-momentum update and the mixing ``W = I - alpha sum_j b_j L_j``.

Nothing here imports the program. A reference takes one node's
parameter tree (the program's layout, made by ``bench/weights.py``) and
the config file's numbers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def dot32(spec: str, a, b):
    """float32 einsum, all passes."""
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _fake_quant(x, dtype):
    """Per-tensor scaled cast to ``dtype`` and back to float32."""
    fmax = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / fmax, 1.0)
    # a float8 cast past the largest finite value gives nan (e4m3fn) or
    # inf (e5m2): keep rounding error at the edge inside the range
    scaled = jnp.clip(x / scale, -fmax, fmax)
    return scaled.astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _q_fwd(x):
    return _fake_quant(x, jnp.float8_e4m3fn)


_q_fwd.defvjp(lambda x: (_fake_quant(x, jnp.float8_e4m3fn), None),
              lambda _, g: (_fake_quant(g, jnp.float8_e5m2),))


@jax.custom_vjp
def _q_bwd(x):
    return x


_q_bwd.defvjp(lambda x: (x, None),
              lambda _, g: (_fake_quant(g, jnp.float8_e5m2),))


def dot8(spec: str, a, b):
    """The control's einsum: operands in scaled float8 (e4m3), the
    cotangents of the backward pass in scaled float8 (e5m2), products
    summed in float32."""
    out = jnp.einsum(spec, _q_fwd(a.astype(jnp.float32)),
                     _q_fwd(b.astype(jnp.float32)), precision=HIGHEST)
    return _q_bwd(out)


DOTS = {"float32": dot32, "float8": dot8}


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def cross_entropy(logits, labels):
    """Mean over tokens of -log softmax(logits)[label]."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def sgd_momentum(params, velocity, grads, lr, momentum):
    """v <- momentum v + g; p <- p - lr v."""
    velocity = jax.tree.map(lambda v, g: momentum * v + g, velocity, grads)
    params = jax.tree.map(lambda p, v: p - lr * v, params, velocity)
    return params, velocity


def partner_table(matchings, nodes):
    """For each matching, each node's partner (itself when unmatched)."""
    table = []
    for edges in matchings:
        partner = list(range(nodes))
        for a, b in edges:
            partner[a], partner[b] = b, a
        table.append(partner)
    return table


def mix(replicas, partners, bits, alpha):
    """x_i <- x_i + alpha sum_j b_j (x_partner_j(i) - x_i) for a list of
    per-node leaves, each on its own device."""
    out = []
    for i, x in enumerate(replicas):
        delta = jnp.zeros_like(x)
        for j, partner in enumerate(partners):
            if bits[j] and partner[i] != i:
                other = jax.device_put(replicas[partner[i]], x.device)
                delta = delta + (other - x)
        out.append(x + alpha * delta)
    return out
