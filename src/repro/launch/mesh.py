"""Mesh construction.

Every mesh of the runtime is built here, with every axis ``Auto``: the
runtime leaves the non-node axes to the compiler inside its node-manual
``shard_map`` regions, which ``jax.make_mesh``'s default ``Explicit``
axes do not allow.

Functions, not module constants: importing this module never touches
jax device state. The dry-run launcher sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax
import; smoke tests and benches see the real (single) device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False, shard: int = 1):
    """16x16 = 256 chips/pod; 2 pods = 512 chips multi-pod.

    ``shard > 1`` carves an FSDP ``shard`` axis out of the within-node
    (model) dimension — total chip count is unchanged; the node's 16
    chips split into ``shard`` replica-shard groups of ``16 // shard``
    tensor-parallel ways each."""
    if 16 % shard:
        raise ValueError(f"shard factor {shard} must divide the 16-chip node")
    model = 16 // shard
    if multi_pod:
        shape: tuple = (2, 16) + ((shard, model) if shard > 1 else (16,))
        axes: tuple = ("pod", "data") + (
            ("shard", "model") if shard > 1 else ("model",)
        )
    else:
        shape = (16,) + ((shard, model) if shard > 1 else (16,))
        axes = ("data",) + (("shard", "model") if shard > 1 else ("model",))
    return make_mesh(shape, axes)


def make_test_mesh(
    *, nodes: int = 4, model: int = 2, shard=None, multi_pod: bool = False
):
    """Small CPU mesh for multi-device unit tests (host device count
    must already be >= nodes*shard*model via XLA_FLAGS). ``shard=N``
    adds the FSDP shard axis between the node and model axes — N may be
    1 (a size-1 axis still selects the sharded runtime); ``None`` omits
    the axis entirely (the replicated runtime)."""
    mid = () if shard is None else (int(shard),)
    mid_ax = () if shard is None else ("shard",)
    if multi_pod:
        return make_mesh(
            (2, nodes // 2) + mid + (model,),
            ("pod", "data") + mid_ax + ("model",),
        )
    return make_mesh((nodes,) + mid + (model,), ("data",) + mid_ax + ("model",))


# Re-export: the node/shard-count authorities live at the dist layer
# (launch sits on top of repro.dist, never the other way around).
from repro.dist.sharding import num_nodes, num_shards  # noqa: E402,F401
