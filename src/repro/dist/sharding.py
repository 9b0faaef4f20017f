"""Logical-axis sharding rules: the single place logical names become
``PartitionSpec``s.

Model code never mentions physical mesh axes. Parameters are declared
with *logical* axis names (see ``repro.models.module``) and activations
are constrained through ``shard(x, ("batch", "seq", "embed"))``. A
``ShardingRules`` object maps each logical name to a physical mesh axis
(or a tuple of axes, or None for replicated); ``use_rules`` makes a
rules object current for the duration of a traced region, and ``shard``
is a no-op when no rules are active — so the same model code runs
unsharded in single-device tests and tensor-parallel under a mesh.

Rule construction is config-aware: a logical dim is only mapped to the
"model" axis when the corresponding config dimension divides the axis
size, so emitted PartitionSpecs are always valid for the actual shapes
(kv_heads=2 on a 4-way TP mesh stays replicated instead of erroring).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

AxisVal = Union[None, str, Tuple[str, ...]]
PyTree = Any


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """A mesh plus the logical-name -> mesh-axis mapping."""

    mesh: Mesh
    mapping: Dict[str, AxisVal]

    def axis(self, name: Optional[str]) -> AxisVal:
        if name is None:
            return None
        return self.mapping.get(name)


# ---------------------------------------------------------------------------
# Current-rules context (trace-time, thread-local)
# ---------------------------------------------------------------------------
_STATE = threading.local()


def current_rules() -> Optional[ShardingRules]:
    """The innermost active ``use_rules`` rules, or None outside any
    (``shard`` is then the identity)."""
    stack = getattr(_STATE, "stack", None)
    return stack[-1] if stack else None


@contextlib.contextmanager
def use_rules(rules: ShardingRules):
    """Make ``rules`` current for ``shard``/constraint resolution."""
    stack = _STATE.__dict__.setdefault("stack", [])
    stack.append(rules)
    try:
        yield rules
    finally:
        stack.pop()


# ---------------------------------------------------------------------------
# Resolution
# ---------------------------------------------------------------------------
def _axes_size(mesh: Mesh, ax: AxisVal) -> int:
    if ax is None:
        return 1
    if isinstance(ax, (tuple, list)):
        n = 1
        for a in ax:
            n *= mesh.shape[a]
        return n
    return mesh.shape[ax]


def logical_to_pspec(
    axes: Sequence[Optional[str]],
    rules: ShardingRules,
    shape: Optional[Sequence[int]] = None,
) -> P:
    """Resolve a tuple of logical axis names to a PartitionSpec.

    When ``shape`` is given, any mapping whose shard count does not
    divide the dim is dropped (replicated). A mesh axis may appear only
    once in a spec; on conflict the earlier dim wins.
    """
    used: set = set()
    parts = []
    for i, name in enumerate(axes):
        ax = rules.axis(name)
        if ax is not None and shape is not None:
            if shape[i] % _axes_size(rules.mesh, ax):
                ax = None
        if ax is not None:
            flat = tuple(ax) if isinstance(ax, (tuple, list)) else (ax,)
            if used & set(flat):
                ax = None
            else:
                used |= set(flat)
        parts.append(tuple(ax) if isinstance(ax, list) else ax)
    return P(*parts)


def shard(x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
    """Constrain ``x`` per the current rules; identity when no rules are
    active, when ranks mismatch (e.g. under extra vmap dims), or when
    the spec resolves fully replicated (also keeps shard_map manual
    bodies constraint-free)."""
    rules = current_rules()
    if rules is None or len(axes) != x.ndim:
        return x
    spec = logical_to_pspec(axes, rules, x.shape)
    if all(a is None for a in spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(rules.mesh, spec))


def param_pspecs(axes_tree: PyTree, rules: ShardingRules) -> PyTree:
    """Map a logical-axes pytree (leaves: tuples of names) to PartitionSpecs."""
    return jax.tree.map(
        lambda axes: logical_to_pspec(axes, rules),
        axes_tree,
        is_leaf=lambda v: isinstance(v, tuple),
    )


def named_shardings(pspec_tree: PyTree, mesh: Mesh) -> PyTree:
    """Bind a PartitionSpec pytree to ``mesh`` as ``NamedSharding``s
    (the form ``jax.device_put`` / ``jax.jit`` placement wants)."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        pspec_tree,
        is_leaf=lambda v: isinstance(v, P),
    )


# ---------------------------------------------------------------------------
# Node count
# ---------------------------------------------------------------------------
def num_nodes(mesh: Mesh, *, multi_pod: bool) -> int:
    """Decentralized node count of ``mesh`` — the single authority every
    layer (specs, gossip, launchers, dryrun) must agree with.

    Raises on a mesh/flag mismatch instead of letting a ``pod``-axis
    mesh with ``multi_pod=False`` silently train on only the ``data``
    slice of the nodes (each pod would gossip within itself and the
    replicas would never mix across pods).
    """
    has_pod = "pod" in mesh.axis_names
    if multi_pod and not has_pod:
        raise ValueError(
            f"multi_pod=True but mesh axes {tuple(mesh.axis_names)} have no "
            "'pod' axis"
        )
    if has_pod and not multi_pod:
        raise ValueError(
            f"mesh has a 'pod' axis ({tuple(mesh.axis_names)}) but "
            "multi_pod=False: this would silently run on "
            f"{mesh.shape['data']} of "
            f"{mesh.shape['data'] * mesh.shape['pod']} nodes — pass "
            "multi_pod=True or use a pod-less mesh"
        )
    n = mesh.shape["data"]
    if multi_pod:
        n *= mesh.shape["pod"]
    return n


def num_shards(mesh: Mesh) -> int:
    """FSDP shard count of ``mesh``: the size of its ``shard`` axis.

    Meshes without the axis run with full replicas (shard factor 1).
    Like ``num_nodes`` this is the single authority — ``repro.dist.fsdp``
    and the launchers must agree on it."""
    if "shard" not in mesh.axis_names:
        return 1
    return int(mesh.shape["shard"])


# ---------------------------------------------------------------------------
# Config-aware rule construction
# ---------------------------------------------------------------------------
def rules_for_config(
    mesh: Mesh,
    cfg: ModelConfig,
    *,
    batch_axes: AxisVal,
    nodes: AxisVal = None,
    kv_seq_sharded: bool = False,
    sequence_parallel: bool = False,
) -> ShardingRules:
    """Build the logical->physical mapping for one config on one mesh."""
    model_ax = "model" if "model" in mesh.axis_names else None
    tp = mesh.shape[model_ax] if model_ax else 1

    def div(n: int) -> bool:
        return model_ax is not None and n > 0 and n % tp == 0

    heads_ok = div(cfg.num_heads)
    kv_ok = div(cfg.num_kv_heads)
    ffn_dims = [d for d in (cfg.d_ff, cfg.moe_d_ff or cfg.d_ff) if d > 0]
    ffn_ok = bool(ffn_dims) and all(div(d) for d in ffn_dims)
    # mamba2 dims (inline to avoid importing repro.models.ssm circularly)
    d_inner = cfg.ssm_expand * cfg.d_model
    ssm_hd = cfg.ssm_head_dim or 64
    ssm_heads = cfg.ssm_num_heads or d_inner // ssm_hd

    mapping: Dict[str, AxisVal] = {
        # activations
        "batch": batch_axes,
        "seq": None,
        "seq_res": model_ax if sequence_parallel else None,
        "embed": None,
        "heads": "model" if heads_ok else None,
        "kv_heads": "model" if kv_ok else None,
        "kv_seq": model_ax if kv_seq_sharded else None,
        "vocab": "model" if div(cfg.padded_vocab) else None,
        "ffn": "model" if ffn_ok else None,
        "ssm_heads": "model" if cfg.ssm_state_dim and div(ssm_heads) else None,
        # parameters
        "heads_proj": "model" if heads_ok else None,
        "kv_proj": "model" if kv_ok else None,
        "q_in": "model" if (not heads_ok and div(cfg.d_model)) else None,
        "kv_in": "model" if (not kv_ok and div(cfg.d_model)) else None,
        "experts": "model" if div(cfg.moe_num_experts) else None,
        "ssm_inner": "model" if cfg.ssm_state_dim and div(d_inner) else None,
        "layers": None,
        # decentralized node axis (train only; None for serving)
        "nodes": nodes,
    }
    return ShardingRules(mesh=mesh, mapping=mapping)


def serve_rules(
    mesh: Mesh,
    cfg: ModelConfig,
    *,
    multi_pod: bool = False,
    kv_seq_sharded: bool = False,
) -> ShardingRules:
    """Serving: batch over the data (and pod) axes, weights tensor-parallel."""
    batch_axes: AxisVal = ("pod", "data") if multi_pod else "data"
    return rules_for_config(
        mesh, cfg, batch_axes=batch_axes, nodes=None,
        kv_seq_sharded=kv_seq_sharded,
    )


def train_rules(
    mesh: Mesh,
    cfg: ModelConfig,
    *,
    multi_pod: bool = False,
    sequence_parallel: bool = False,
) -> ShardingRules:
    """Decentralized training: the leading stacked dim shards over the
    node axes; each node's local batch stays unsharded (per-node data)."""
    nodes: AxisVal = ("pod", "data") if multi_pod else "data"
    return rules_for_config(
        mesh, cfg, batch_axes=None, nodes=nodes,
        sequence_parallel=sequence_parallel,
    )
