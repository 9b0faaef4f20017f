"""Weights, tokens and schedule bits made from the seed.

The benchmark makes these itself and hands them to the program and to
the reference alike. Each leaf's values depend only on the seed and the
leaf's path, so one leaf can be made again on its own (the parameter
change after three steps is read leaf by leaf against it).

Initial values by leaf name, after the published recipes: norm scales
and Mamba2's D are ones; A_log is log U(1, 16); dt_bias is the inverse
softplus of a log-uniform dt in [1e-3, 1e-1]; conv biases are zero and
conv weights U(+-1/sqrt(width)); embedding and head tables N(0, 0.02);
every other matrix N(0, 1/fan_in).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def base_key(seed: int):
    """A threefry key from any whole number (seeds may pass 32 bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def leaf_paths(tree) -> list:
    """Dotted paths of the tree's leaves, in ``jax.tree`` order."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [".".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in flat]


def _leaf(key, path: str, shape, dtype):
    last = path.rsplit(".", 1)[-1]
    if last in ("scale", "norm_scale", "D"):
        return jnp.ones(shape, dtype)
    if last == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                       ).astype(dtype)
    if last == "dt_bias":
        lo, hi = math.log(1e-3), math.log(1e-1)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return jnp.log(jnp.expm1(dt)).astype(dtype)
    if last == "conv_b":
        return jnp.zeros(shape, dtype)
    if last == "conv_w":
        bound = 1.0 / math.sqrt(shape[-2])
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound
                                  ).astype(dtype)
    if path in ("embed.table", "unembed.w"):
        return (0.02 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    if last == "w":
        fan_in = shape[-2]
        return (jax.random.normal(key, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dtype)
    raise ValueError(f"no initial value is defined for leaf {path!r}")


def make_leaf(seed_key, index: int, path: str, shape, dtype):
    return _leaf(jax.random.fold_in(seed_key, index), path, shape, dtype)


def replica(seed_key, abstract):
    """One node's parameters for the abstract tree ``abstract``."""
    leaves, treedef = jax.tree.flatten(abstract)
    paths = leaf_paths(abstract)
    made = [make_leaf(seed_key, i, p, a.shape, a.dtype)
            for i, (p, a) in enumerate(zip(paths, leaves))]
    return jax.tree.unflatten(treedef, made)


def stacked_params(seed: int, abstract, nodes: int, shardings):
    """Every node starts from the same replica (DecenSGD's start), made
    on the devices in one jitted call."""
    def make(key):
        one = replica(key, abstract)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (nodes,) + a.shape), one)

    return jax.jit(make, out_shardings=shardings)(base_key(seed))


def token_maker(seed: int, vocab: int, nodes: int, batch: int, seq: int,
                sharding, bits_table, bits_sharding):
    """Jitted ``k -> (batch dict, bits)`` for step ``k``: tokens uniform
    over the vocabulary, each node its own rows, labels the next token;
    bits the schedule row of step ``k``."""
    key = base_key(seed)
    table = jnp.asarray(bits_table, jnp.float32)
    rows = table.shape[0]

    def make(k):
        ids = jax.random.randint(
            jax.random.fold_in(jax.random.fold_in(key, 0x70C), k),
            (nodes, batch, seq + 1), 0, vocab, jnp.int32)
        batch_ = {"tokens": ids[..., :-1], "labels": ids[..., 1:]}
        return batch_, table[k % rows]

    return jax.jit(make, out_shardings=(
        {"tokens": sharding, "labels": sharding}, bits_sharding))


def schedule_bits(seed: int, probabilities, rows: int) -> np.ndarray:
    """(rows, M) activation bits: matching j active with its probability,
    independently per step and matching (MATCHA's sampling)."""
    p = np.asarray(probabilities, np.float64)
    if p.size == 0:
        return np.zeros((rows, 0), np.float32)
    rng = np.random.default_rng([int(seed), 0xB175])
    return (rng.random((rows, p.size)) < p).astype(np.float32)
