"""FSDP-style sharded replicas on the gossip bucket layout.

The replicated runtime (``repro.dist.decen_train``) keeps a full fp32
parameter copy plus full optimizer state on every node, so per-device
memory is O(model) no matter how many devices the mesh has — the large
registry configs OOM exactly there. This module shards each node's
replica over a ``shard`` mesh axis of size S using the same contiguous
fp32 buckets the overlap gossip mode introduced
(``repro.dist.bucketing`` with ``pad_to=S``): one device keeps one
``(bucket_size // S,)`` slice of every bucket, and the optimizer state
lives on the slices too, so per-device training state is O(model / S).

One train step (per Wang et al. 2024's bucketed-contiguous layout):

    all-gather(bucket shards over "shard")  ->  unravel to the param tree
    fwd/bwd on the node's batch slice       ->  grads
    ravel(grads) -> reduce-scatter(mean)    ->  grad shards
    elementwise optimizer update            ->  new param shards
    gossip ppermutes directly on the shards ->  consensus correction

Gossip composes with the sharding for free: every matching's ppermute
runs over the node axes only, so shard s of node i exchanges with shard
s of its partner and each matching moves 1/S of the replicated-mode
bytes — MATCHA's communication saving and FSDP's memory saving multiply.
The node's batch is split over the shard axis (``batch_per_node`` must
divide by S), so the reduce-scatter both averages the sub-batch grads
and leaves each device exactly its slice.

Parameters are held as fp32 master shards (the gossip/consensus dtype);
the all-gathered tree is cast back to the declared param dtype before
the fwd/bwd. With fp32 params (every registry config trains fp32) a
``--shard 1`` mesh replays the replicated step's arithmetic exactly.

Execution strategies mirror the replicated runtime: ``"sequential"``
(in-step masked exchange, one executable for the whole schedule — the
analogue of ``gossip_mode="masked"``), ``"overlap"`` (one-step-delayed
exchange carried in the same ``GossipState`` container, flushed by
``make_fsdp_gossip_flush``), and ``"none"`` (local SGD only).

Two materialization strategies choose how the fwd/bwd sees the params:

``FsdpLayout`` (monolithic): one all-gather re-materializes the whole
model before the fwd — peak transient memory O(model) per device and
the gather serializes in front of the compute.

``FsdpStreamLayout`` (streaming, ``make_stream_layout``): buckets follow
the model's *layer groups* (``Model.param_group_specs`` — one group per
transformer block plus embed/encoder/head groups), and the step walks
``Model.stream_stages`` gathering one group at a time. Each stage is a
remat closure over the group's *shards*, so the backward pass
re-gathers the group instead of keeping its full-size view live, and
the gathered grads arrive pre-reduce-scattered through the all-gather
transpose (``psum_scatter`` over the shard axis) — peak transient
memory drops to O(largest group) and each gather can hide behind the
previous block's compute. Resident state (shards, optimizer, gossip)
is identical in both layouts: a flat tuple of contiguous fp32 bucket
shards, so gossip, checkpoints and the overlap ``GossipState`` are
layout-agnostic.

Scan-aware streaming (``make_stream_layout(scan_aware=True)``, the
default) extends the walk *inside* ``lax.scan`` segments. A scanned /
periodic segment used to collapse into one near-model-sized group (its
scan consumes the whole stacked subtree); its bucket is now laid out as
``repeats`` shard-major per-layer rows (``bucketing.scan_ravel``), and
the step runs the segment through ``_scan_stream_segment``: a
``jax.custom_vjp``-wrapped ``lax.scan`` whose carry threads the *next*
layer's in-flight gathered row, so iteration i computes on layer i's
params while layer i+1's all-gather is already issued — explicit
double-buffered prefetch, not scheduler-dependent. The backward pass
re-gathers each layer's row per iteration (reverse scan over a
recomputed forward) and reduce-scatters each row's grad through the
all-gather transpose, so at most two layer rows are ever live and peak
transient memory is O(layer) even for deep scanned stacks. The resident
bucket-shard tuple contract is unchanged — gossip, the optimizer,
``GossipState`` and checkpoints see the same flat fp32 shards (the
shard-major row order is a fixed in-bucket permutation applied
consistently by the layout's ravel/unravel).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.dist import bucketing
from repro.dist.decen_train import DistSpec, GossipState
from repro.dist.gossip import (
    apply_gossip,
    delayed_delta,
    launch_matchings_masked,
    mix_matchings_masked,
)
from repro.optim.optimizers import Optimizer, apply_updates

PyTree = Any

FSDP_GOSSIP_MODES = ("sequential", "overlap", "none")

# --- static-analysis contract (consumed by repro.analysis.checks) ----------
# Sharding collectives run over the "shard" axis only: the all-gather
# that re-materializes bucket shards, its transpose (psum_scatter) that
# reduce-scatters grads, and the psum/pmean reductions for clipping and
# loss logging. Gossip's ppermutes (declared in repro.dist.gossip) stay
# on the node axes — that separation is what makes MATCHA's per-matching
# saving and FSDP's 1/S byte saving multiply.
COLLECTIVE_CONTRACT = {
    "all_gather": {"axes": ("shard",)},
    "psum_scatter": {"axes": ("shard",)},
    "psum": {"axes_subset_of": ("shard", "model")},
}
# Fp32-widening accumulation points (see repro.dist.gossip for the rest
# of the gossip path). Bucket shards themselves are always fp32; the
# only upcasts here widen logging reductions.
FP32_UPCAST_SITES = (
    "consensus_distance_sharded",
)


def _cast_like(tree: PyTree, abs_like: PyTree) -> PyTree:
    """fp32 unravel output -> declared storage dtypes (shapes untouched,
    so this also works leafwise on node-stacked trees)."""
    return jax.tree.map(lambda x, a: x.astype(a.dtype), tree, abs_like)


def _group_subtree(tree: PyTree, group, *, stacked: bool = False) -> PyTree:
    """Select one layer group out of a (possibly node-stacked) param
    tree: the group's top-level keys, sliced to ``group.layer`` along
    the segment's stacked layer dim for unrolled-block groups."""
    sub = {k: tree[k] for k in group.keys}
    if group.layer is not None:
        idx = (slice(None), group.layer) if stacked else (group.layer,)
        sub = jax.tree.map(lambda a: a[idx], sub)
    return sub


def _join_group_subtrees(
    groups, subtrees: Tuple[PyTree, ...], *, stacked: bool = False
) -> PyTree:
    """Inverse of ``_group_subtree`` over a full group cover: re-stack
    the per-layer block slices along the segment layer dim and merge the
    whole-tree groups back into one top-level dict."""
    out: dict = {}
    sliced: dict = {}
    for g, sub in zip(groups, subtrees):
        if g.layer is None:
            out.update(sub)
        else:
            for k in g.keys:
                sliced.setdefault(k, {})[g.layer] = sub[k]
    axis = 1 if stacked else 0
    for k, by_layer in sliced.items():
        ordered = [by_layer[i] for i in range(len(by_layer))]
        out[k] = jax.tree.map(
            lambda *xs: jnp.stack(xs, axis=axis), *ordered
        )
    return out


@dataclasses.dataclass(frozen=True)
class FsdpLayout:
    """Static sharded-replica layout: the bucket plan (padded to the
    shard factor) plus the abstract per-node param tree it was built
    from (shapes + storage dtypes for the materialize cast). Buckets are
    byte-target-sized; the train step re-materializes the whole model
    with one all-gather per bucket (monolithic strategy)."""

    plan: bucketing.BucketPlan
    abs_local: PyTree             # ShapeDtypeStructs of one node's params
    num_nodes: int
    num_shards: int

    @property
    def shard_sizes(self) -> Tuple[int, ...]:
        return tuple(s // self.num_shards for s in self.plan.bucket_sizes)

    @property
    def per_device_elements(self) -> int:
        return sum(self.shard_sizes)

    # -- bucket tuple <-> param tree (local / node-stacked) ------------------
    def ravel(self, tree: PyTree) -> Tuple[jax.Array, ...]:
        return bucketing.ravel(self.plan, tree)

    def unravel_cast(self, buckets: Tuple[jax.Array, ...]) -> PyTree:
        return _cast_like(
            bucketing.unravel(self.plan, buckets), self.abs_local
        )

    def ravel_stacked(self, tree: PyTree) -> Tuple[jax.Array, ...]:
        return bucketing.ravel_stacked(self.plan, tree)

    def unravel_stacked(self, buckets: Tuple[jax.Array, ...]) -> PyTree:
        """fp32 node-stacked tree (optimizer-slot layout — no storage
        cast)."""
        return bucketing.unravel_stacked(self.plan, buckets)

    def unravel_stacked_cast(self, buckets: Tuple[jax.Array, ...]) -> PyTree:
        return _cast_like(self.unravel_stacked(buckets), self.abs_local)


@dataclasses.dataclass(frozen=True)
class FsdpStreamLayout:
    """Layer-grouped sharded-replica layout (streaming strategy): bucket
    i holds layer group i (``Model.param_group_specs`` order), so the
    train step can gather group g+1 while computing group g and peak
    transient memory is O(largest group). Same resident bucket-shard
    tuple contract as ``FsdpLayout`` — gossip/opt/checkpoint code takes
    either."""

    plan: bucketing.GroupedPlan
    groups: Tuple[Any, ...]       # Model.param_group_specs() entries
    abs_local: PyTree
    abs_groups: Tuple[PyTree, ...]
    num_nodes: int
    num_shards: int
    # Per-layer abstract subtree per scan-aware group (leading scan dim
    # stripped); None for whole-subtree groups. Defaults to all-None.
    abs_rows: Tuple[Any, ...] = ()

    def __post_init__(self):
        if not self.abs_rows:
            object.__setattr__(
                self, "abs_rows", (None,) * len(self.groups)
            )

    @property
    def shard_sizes(self) -> Tuple[int, ...]:
        return tuple(s // self.num_shards for s in self.plan.bucket_sizes)

    @property
    def per_device_elements(self) -> int:
        return sum(self.shard_sizes)

    @property
    def group_names(self) -> Tuple[str, ...]:
        return self.plan.names

    # -- bucket tuple <-> param tree (local / node-stacked) ------------------
    def ravel(self, tree: PyTree) -> Tuple[jax.Array, ...]:
        out = []
        for g, p, r in zip(self.groups, self.plan.plans, self.plan.repeats):
            sub = _group_subtree(tree, g)
            if r > 1:
                out.append(bucketing.scan_ravel(p, sub, r, self.num_shards))
            else:
                out.append(bucketing.ravel(p, sub)[0])
        return tuple(out)

    def unravel_cast(self, buckets: Tuple[jax.Array, ...]) -> PyTree:
        subs = []
        for p, b, a, r in zip(
            self.plan.plans, buckets, self.abs_groups, self.plan.repeats
        ):
            if r > 1:
                sub = bucketing.scan_unravel(p, b, r, self.num_shards)
            else:
                sub = bucketing.unravel(p, (b,))
            subs.append(_cast_like(sub, a))
        return _join_group_subtrees(self.groups, tuple(subs))

    def ravel_stacked(self, tree: PyTree) -> Tuple[jax.Array, ...]:
        out = []
        for g, p, r in zip(self.groups, self.plan.plans, self.plan.repeats):
            sub = _group_subtree(tree, g, stacked=True)
            if r > 1:
                out.append(
                    bucketing.scan_ravel_stacked(p, sub, r, self.num_shards)
                )
            else:
                out.append(bucketing.ravel_stacked(p, sub)[0])
        return tuple(out)

    def unravel_stacked(self, buckets: Tuple[jax.Array, ...]) -> PyTree:
        """fp32 node-stacked tree (optimizer-slot layout — no storage
        cast)."""
        subs = []
        for p, b, r in zip(self.plan.plans, buckets, self.plan.repeats):
            if r > 1:
                subs.append(
                    bucketing.scan_unravel_stacked(p, b, r, self.num_shards)
                )
            else:
                subs.append(bucketing.unravel_stacked(p, (b,)))
        return _join_group_subtrees(self.groups, tuple(subs), stacked=True)

    def unravel_stacked_cast(self, buckets: Tuple[jax.Array, ...]) -> PyTree:
        return _cast_like(self.unravel_stacked(buckets), self.abs_local)


AnyFsdpLayout = Union[FsdpLayout, FsdpStreamLayout]


def _abs_params(model) -> PyTree:
    abs_local = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    for leaf in jax.tree.leaves(abs_local):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            raise ValueError(
                "fsdp mode shards every param leaf into the fp32 buckets; "
                f"non-float leaf of dtype {leaf.dtype} cannot be sharded"
            )
    return abs_local


def make_layout(
    model,
    spec: DistSpec,
    *,
    target_bytes: int = bucketing.DEFAULT_TARGET_BYTES,
) -> FsdpLayout:
    """Monolithic bucket layout of one node's parameters,
    shard-divisible."""
    abs_local = _abs_params(model)
    plan = bucketing.plan_buckets(
        abs_local, target_bytes=target_bytes, pad_to=spec.num_shards
    )
    return FsdpLayout(
        plan=plan,
        abs_local=abs_local,
        num_nodes=spec.num_nodes,
        num_shards=spec.num_shards,
    )


def param_group_subtrees(
    model, *, abs_local: PyTree = None, groups=None
) -> Tuple[Tuple[str, PyTree], ...]:
    """(name, abstract subtree) per layer group of ``model`` — the
    input ``bucketing.plan_group_buckets`` takes. Public so benches and
    tools can reason about the streamed layout (group count, largest
    group) without building a mesh or a ``DistSpec``. Pass ``abs_local``
    / ``groups`` when already computed — the ``model.init`` eval_shape
    is the expensive part of layout construction on large configs and
    must not be traced twice."""
    if abs_local is None:
        abs_local = _abs_params(model)
    if groups is None:
        groups = tuple(model.param_group_specs())
    return tuple(
        (g.name, jax.eval_shape(lambda t, _g=g: _group_subtree(t, _g),
                                abs_local))
        for g in groups
    )


def make_stream_layout(
    model, spec: DistSpec, *, scan_aware: bool = True
) -> FsdpStreamLayout:
    """Layer-grouped bucket layout: one shard-divisible bucket per
    entry of ``model.param_group_specs()`` (execution order).

    ``scan_aware=True`` (default) lays a scanned/periodic segment's
    bucket out as ``repeats`` shard-major per-layer rows so the train
    step gathers one scan iteration's params at a time; ``False`` keeps
    the stack-at-once layout (one monolithic gather per scanned
    segment — the pre-scan-streaming behavior, for A/B comparison)."""
    abs_local = _abs_params(model)
    groups = tuple(model.param_group_specs())
    named = param_group_subtrees(model, abs_local=abs_local, groups=groups)
    abs_groups = tuple(a for _, a in named)
    scan_repeats = tuple(g.repeats for g in groups)
    gplan = bucketing.plan_group_buckets(
        list(named),
        pad_to=spec.num_shards,
        scan_aware=scan_aware,
        scan_repeats=scan_repeats,
    )
    abs_rows = tuple(
        bucketing._strip_leading(sub, r, name) if r > 1 else None
        for (name, sub), r in zip(named, gplan.repeats)
    )
    return FsdpStreamLayout(
        plan=gplan,
        groups=groups,
        abs_local=abs_local,
        abs_groups=abs_groups,
        num_nodes=spec.num_nodes,
        num_shards=spec.num_shards,
        abs_rows=abs_rows,
    )


# ---------------------------------------------------------------------------
# State init + shardings: every array carries leading (nodes, shards) dims
# ---------------------------------------------------------------------------
def _stack2(layout: AnyFsdpLayout, tree: PyTree) -> PyTree:
    n, s = layout.num_nodes, layout.num_shards
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None, None], (n, s) + a.shape), tree
    )


def init_fsdp_params(
    model, layout: AnyFsdpLayout, seed: int = 0
) -> Tuple[jax.Array, ...]:
    """Sharded replicas of one init: per bucket ``(nodes, S, size // S)``
    fp32 — every node starts from the same point, like the replicated
    ``init_stacked_params``."""
    params = model.init(jax.random.key(seed))
    buckets = layout.ravel(params)
    shards = bucketing.shard_buckets(buckets, layout.num_shards)
    n = layout.num_nodes
    return tuple(
        jnp.broadcast_to(s[None], (n,) + s.shape) for s in shards
    )


def _abs_shards(layout: AnyFsdpLayout) -> Tuple[jax.ShapeDtypeStruct, ...]:
    return tuple(
        jax.ShapeDtypeStruct((sz,), jnp.float32) for sz in layout.shard_sizes
    )


def init_fsdp_opt_state(opt: Optimizer, layout: AnyFsdpLayout) -> PyTree:
    """Optimizer state over the param *shards*: param-shaped slots
    (velocity, mu, nu) are per-shard fp32 slices, scalar slots (step)
    broadcast — all stacked ``(nodes, S, ...)``."""
    zeros = tuple(
        jnp.zeros((sz,), jnp.float32) for sz in layout.shard_sizes
    )
    return _stack2(layout, opt.init(zeros))


def fsdp_param_pspecs(spec: DistSpec, layout: AnyFsdpLayout):
    """PartitionSpecs for the bucket-shard tuple: every bucket is
    ``(nodes, S, slice)``, sharded ``P(nodes, "shard")``."""
    nodes = spec.nodes_axis
    return tuple(
        P(nodes, "shard") for _ in range(layout.plan.num_buckets)
    )


def fsdp_opt_pspecs(opt: Optimizer, spec: DistSpec, layout: AnyFsdpLayout):
    """PartitionSpecs for the sharded optimizer state: every slot
    (param-shaped or scalar, both stacked ``(nodes, S, ...)``) shards
    ``P(nodes, "shard")``."""
    state_abs = jax.eval_shape(opt.init, _abs_shards(layout))
    nodes = spec.nodes_axis
    return jax.tree.map(lambda _: P(nodes, "shard"), state_abs)


def init_fsdp_gossip_state(layout: AnyFsdpLayout) -> GossipState:
    """Empty in-flight buffer for the overlap mode, on the shard slices."""
    n, s = layout.num_nodes, layout.num_shards
    return GossipState(
        delta=tuple(
            jnp.zeros((n, s, sz), jnp.float32) for sz in layout.shard_sizes
        ),
    )


def fsdp_gossip_state_pspecs(spec: DistSpec, layout: AnyFsdpLayout) -> GossipState:
    """PartitionSpecs for the overlap-mode ``GossipState``: one
    ``P(nodes, "shard")`` per in-flight fp32 bucket-shard delta."""
    nodes = spec.nodes_axis
    return GossipState(
        delta=tuple(P(nodes, "shard") for _ in range(layout.plan.num_buckets))
    )


def consensus_distance_sharded(shards: Tuple[jax.Array, ...]):
    """``decen_train.consensus_distance`` computed directly on the
    ``(nodes, S, slice)`` shard arrays — the squared node-deviations
    decompose over the contiguous slices, so the replica spread can be
    logged without gathering full O(model) copies (the whole point of
    the shard mode). Padding contributes zero: it starts identical on
    every node and stays identical (zero grads, zero gossip delta)."""
    acc = None
    for s in shards:
        x = s.astype(jnp.float32)
        mu = x.mean(axis=0, keepdims=True)
        d = jnp.sum((x - mu) ** 2, axis=(1, 2))
        acc = d if acc is None else acc + d
    if acc is None:
        return jnp.float32(0.0)
    return jnp.sqrt(jnp.mean(acc))


# ---------------------------------------------------------------------------
# Gather / scatter: checkpoint + eval interop with the replicated layout
# ---------------------------------------------------------------------------
def gather_params(
    layout: AnyFsdpLayout, shards: Tuple[jax.Array, ...]
) -> PyTree:
    """Sharded replicas back to the node-stacked param tree (leaves cast
    to their declared storage dtype) — the exact layout the replicated
    runtime and ``checkpoint.ckpt.save_run`` use, so fsdp checkpoints are
    interchangeable with replicated ones at any shard factor AND at any
    bucket layout (monolithic or layer-grouped): the on-disk format is
    always the gathered stacked tree."""
    full = bucketing.unshard_buckets(shards)          # (nodes, size) each
    return layout.unravel_stacked_cast(full)


def scatter_params(
    layout: AnyFsdpLayout, stacked_params: PyTree
) -> Tuple[jax.Array, ...]:
    """Node-stacked param tree to sharded replicas (restore path)."""
    buckets = layout.ravel_stacked(stacked_params)
    return bucketing.shard_buckets(buckets, layout.num_shards)


def _is_bucket_slot(layout: AnyFsdpLayout, sub: PyTree) -> bool:
    probe = tuple(range(layout.plan.num_buckets))
    return jax.tree.structure(sub) == jax.tree.structure(probe)


def gather_opt_state(layout: AnyFsdpLayout, sharded_state: PyTree) -> PyTree:
    """Sharded optimizer state to the replicated stacked layout
    (param-shaped slots back to leaf trees, scalar slots to (nodes,))."""
    out = {}
    for key, sub in sharded_state.items():
        if _is_bucket_slot(layout, sub):
            full = bucketing.unshard_buckets(tuple(sub))
            out[key] = layout.unravel_stacked(full)
        else:
            out[key] = jax.tree.map(lambda a: a[:, 0], sub)
    return out


def scatter_opt_state(
    layout: AnyFsdpLayout, opt: Optimizer, stacked_state: PyTree
) -> PyTree:
    """Replicated stacked optimizer state to the sharded layout."""
    params_struct = jax.tree.structure(layout.abs_local)
    s = layout.num_shards
    out = {}
    for key, sub in stacked_state.items():
        if jax.tree.structure(sub) == params_struct:
            buckets = layout.ravel_stacked(sub)
            out[key] = bucketing.shard_buckets(buckets, s)
        else:
            out[key] = jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a[:, None], (a.shape[0], s) + a.shape[1:]
                ),
                sub,
            )
    return out


# ---------------------------------------------------------------------------
# In-body pieces (run inside shard_map, manual over node axes + "shard")
# ---------------------------------------------------------------------------
def _materialize(layout: FsdpLayout, shards: Tuple[jax.Array, ...]) -> PyTree:
    """all-gather the bucket shards over the shard axis and unravel to a
    full per-node param tree in storage dtype (the fwd/bwd view)."""
    full = tuple(
        jax.lax.all_gather(s, "shard", tiled=True) for s in shards
    )
    return layout.unravel_cast(full)


def _materialize_group(
    layout: FsdpStreamLayout, gi: int, shard: jax.Array
) -> PyTree:
    """all-gather ONE layer group's bucket shard and unravel it to the
    group's param subtree in storage dtype. The only full-size view the
    streamed step ever holds is one group's. A scan-aware group's
    bucket is shard-major rows — this is its stack-at-once fallback
    (used by stages that cannot scan-stream, e.g. cross-attention)."""
    full = jax.lax.all_gather(shard, "shard", tiled=True)
    r = layout.plan.repeats[gi]
    if r > 1:
        sub = bucketing.scan_unravel(
            layout.plan.plans[gi], full, r, layout.num_shards
        )
    else:
        sub = bucketing.unravel(layout.plan.plans[gi], (full,))
    return _cast_like(sub, layout.abs_groups[gi])


def _scan_stream_segment(layout: FsdpStreamLayout, gi: int, body):
    """Per-iteration streamed execution of one scanned segment.

    Returns ``f(x, rows) -> (x, aux)`` where ``rows`` is the group's
    resident shard slice viewed as ``(repeats, per_layer // S)`` rows.
    Forward is a ``lax.scan`` whose carry threads the NEXT layer's
    gathered row: iteration i computes on layer i's params while layer
    i+1's all-gather is already issued (explicit double-buffered
    prefetch — exactly two ``(per_layer,)`` rows live, independent of
    the scheduler).

    ``jax.custom_vjp`` keeps autodiff from defeating the streaming: a
    plain ``lax.scan`` over a carried gathered row would stack the rows
    into an ``(repeats, per_layer)`` residual — the whole segment,
    precisely what streaming exists to avoid. Instead the backward rule
    recomputes the forward storing only each iteration's residual-stream
    input, then runs a reverse scan that re-gathers layer i's row,
    differentiates that one layer (``jax.vjp``), and reduce-scatters the
    row's grad through the all-gather transpose (``psum_scatter`` over
    the shard axis) — the same sum-over-sub-batches arithmetic the
    non-scan streamed stages produce, so the caller's uniform ``/S``
    turns it into the mean. The row grads come back ``(repeats,
    per_layer // S)``, matching the resident layout.
    """
    per_plan = layout.plan.plans[gi]
    abs_row = layout.abs_rows[gi]
    reps = layout.plan.repeats[gi]

    def gather_row(rows, i):
        sl = jax.lax.dynamic_index_in_dim(rows, i, axis=0, keepdims=False)
        return jax.lax.all_gather(sl, "shard", tiled=True)

    def one_layer(x, raw):
        view = _cast_like(bucketing.unravel(per_plan, (raw,)), abs_row)
        return body.apply_layer(x, view)

    def run_fwd(x, rows):
        buf0 = gather_row(rows, 0)

        def step(carry, i):
            x, buf = carry
            # issue layer i+1's gather BEFORE touching layer i's params
            nxt = gather_row(rows, jnp.minimum(i + 1, reps - 1))
            x, aux = one_layer(x, buf)
            return (x, nxt), aux

        (x, _), auxs = jax.lax.scan(step, (x, buf0), jnp.arange(reps))
        return x, jax.tree.map(lambda a: a.sum(), auxs)

    @jax.custom_vjp
    def f(x, rows):
        return run_fwd(x, rows)

    def f_fwd(x, rows):
        return run_fwd(x, rows), (x, rows)

    def f_bwd(res, cts):
        x0, rows = res
        dx, daux = cts

        def fstep(x, i):
            x_new, _ = one_layer(x, gather_row(rows, i))
            return x_new, x               # stash layer i's INPUT stream

        _, x_ins = jax.lax.scan(fstep, x0, jnp.arange(reps))

        def rstep(dx, idx_x):
            i, x_in = idx_x
            raw = gather_row(rows, i)

            def g(x, raw):
                view = _cast_like(
                    bucketing.unravel(per_plan, (raw,)), abs_row
                )
                return body.apply_layer(x, view)

            _, vjp = jax.vjp(g, x_in, raw)
            dx_new, draw = vjp((dx, daux))
            drow = jax.lax.psum_scatter(
                draw, "shard", scatter_dimension=0, tiled=True
            )
            return dx_new, drow

        dx0, drows = jax.lax.scan(
            rstep, dx, (jnp.arange(reps), x_ins), reverse=True
        )
        return dx0, drows

    f.defvjp(f_fwd, f_bwd)
    return f


def _acc_aux(aux, new):
    return {k: aux[k] + new[k] for k in aux}


def _stream_loss(
    model, layout: FsdpStreamLayout, shards: Tuple[jax.Array, ...], batch
):
    """Streamed fwd+loss over the model's layer groups.

    Each stage runs as a ``jax.checkpoint`` closure whose inputs are the
    carry and the *shards* of the groups it reads — the all-gather
    happens inside the remat boundary, so the backward pass re-gathers
    the group instead of keeping its full-size view live, and the
    cotangent flowing back into a shard is the group's grad already
    psum-scattered over the shard axis (the all-gather transpose): the
    per-group reduce-scatter the monolithic path issues explicitly.
    The gathers of later stages depend only on the resident shards, so
    the latency-hiding scheduler can overlap group g+1's gather with
    group g's compute.

    A stage carrying a :class:`~repro.models.transformer.ScanStreamBody`
    over a scan-aware group runs through ``_scan_stream_segment``
    instead: per-iteration row gather with double-buffered prefetch,
    per-iteration backward re-gather — its ``custom_vjp`` already owns
    the rematerialization, so no outer ``jax.checkpoint``.
    """
    stages = model.stream_stages(batch)
    carry = {"batch": batch}
    for st in stages:
        if st.scan is not None and len(st.group_ids) == 1:
            gi = st.group_ids[0]
            reps = layout.plan.repeats[gi]
            if reps > 1:
                if reps != st.scan.repeats:
                    raise ValueError(
                        f"group {layout.plan.names[gi]!r}: layout planned "
                        f"{reps} scan rows but the model's scan body has "
                        f"{st.scan.repeats} iterations"
                    )
                rows = shards[gi].reshape(reps, -1)
                seg_fn = _scan_stream_segment(layout, gi, st.scan)
                x, aux = seg_fn(carry["x"], rows)
                carry = {**carry, "x": x,
                         "aux": _acc_aux(carry["aux"], aux)}
                continue

        def run(carry, *gshards, _st=st):
            trees = tuple(
                _materialize_group(layout, gi, sh)
                for gi, sh in zip(_st.group_ids, gshards)
            )
            return _st.apply(carry, trees)

        carry = jax.checkpoint(run)(
            carry, *(shards[gi] for gi in st.group_ids)
        )
    return carry["loss"], carry["metrics"]


def _reduce_scatter_grads(
    layout: FsdpLayout, grads: PyTree
) -> Tuple[jax.Array, ...]:
    """ravel the grad tree and reduce-scatter over the shard axis: each
    device gets the mean of the S sub-batch grads, sliced to its shard
    (mean over sub-batches == the full-batch grad of the token-mean
    loss, since the batch splits evenly)."""
    s = layout.num_shards
    buckets = layout.ravel(grads)
    out = []
    for g in buckets:
        r = jax.lax.psum_scatter(g, "shard", scatter_dimension=0, tiled=True)
        out.append(r / s if s > 1 else r)
    return tuple(out)


def _clip_sharded(
    g_shards: Tuple[jax.Array, ...], max_norm: float
) -> Tuple[jax.Array, ...]:
    """Global-norm clip of the *full* per-node gradient from its shards:
    local sum-of-squares psum'd over the shard axis, one scale."""
    sq = sum(jnp.sum(jnp.square(g)) for g in g_shards)
    norm = jnp.sqrt(jax.lax.psum(sq, "shard"))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return tuple(g * scale for g in g_shards)


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------
def make_fsdp_train_step(
    model,
    opt: Optimizer,
    plan,                                 # repro.core.MatchaPlan
    spec: DistSpec,
    layout: AnyFsdpLayout,
    *,
    gossip_mode: str = "sequential",
    grad_clip: float = 0.0,
    faulted: bool = False,
):
    """Build the jitted sharded-replica decentralized step.

    The fwd/bwd materialization strategy follows the layout:
    ``FsdpLayout`` re-materializes the whole model with one monolithic
    all-gather; ``FsdpStreamLayout`` walks the model's layer groups,
    gathering one group at a time (O(largest group) peak transient
    memory, per-group reduce-scatter through the remat'd all-gather
    transpose). Everything around the fwd/bwd — optimizer on the
    shards, gossip on the bucket shards, the overlap ``GossipState`` —
    is identical in both, because both layouts expose the same flat
    bucket-shard tuple.

    For ``gossip_mode`` in ("sequential", "none"):

        shards, opt_state, losses, metrics = step(shards, opt_state,
                                                  batch, bits)

    For ``gossip_mode="overlap"`` the step threads the in-flight
    exchange exactly like the replicated overlap mode:

        shards, opt_state, gstate, losses, metrics = step(
            shards, opt_state, gstate, batch, bits)

    ``shards`` is the tuple from ``init_fsdp_params`` (per bucket
    ``(nodes, S, size // S)`` fp32); ``opt_state`` from
    ``init_fsdp_opt_state``; ``batch`` leaves are
    ``(nodes, batch_per_node, ...)`` with ``batch_per_node % S == 0``
    (split over the shard axis in-step); ``bits`` the (M,) activation
    row. ``losses``/``metrics`` come back ``(nodes, S)`` with identical
    columns (pmean'd over the shard axis). The step donates ``shards``,
    ``opt_state`` (and ``gstate``): the caller must not read the arrays
    it passed in after the call.

    ``faulted=True`` is the link-failure-tolerant variant (mirroring
    ``decen_train.make_train_step``): ``bits`` becomes the per-node
    ``(nodes, M)`` effective activation array, sharded over the node
    axes (replicated over "shard" — every shard of a node sees the same
    gates, so the whole replica degrades coherently) and stripped to the
    node's own (M,) row inside the body. Gossip arithmetic is unchanged;
    all-ones gates reproduce the default step bit-for-bit.
    """
    if gossip_mode == "masked":            # replicated-runtime spelling
        gossip_mode = "sequential"
    if gossip_mode not in FSDP_GOSSIP_MODES:
        raise ValueError(
            f"unknown fsdp gossip_mode {gossip_mode!r}; "
            f"choose from {FSDP_GOSSIP_MODES}"
        )
    if spec.num_shards != layout.num_shards:
        raise ValueError(
            f"spec mesh has shard factor {spec.num_shards} but the layout "
            f"was built for {layout.num_shards}"
        )
    info = spec.node_info
    nodes_ax = spec.nodes_axis
    mesh = spec.mesh
    manual = set(spec.node_axes) | {"shard"}
    perms = np.asarray(plan.permutations)
    alpha = float(plan.alpha)
    streaming = isinstance(layout, FsdpStreamLayout)
    num_shards = layout.num_shards

    def grads_of(ps, b):
        if streaming:
            # grads arrive per group, already psum-scattered (summed)
            # over the shard axis by the all-gather transpose; the /S
            # turns the sum of the S sub-batch grads into their mean —
            # the same arithmetic _reduce_scatter_grads applies. The
            # per-group gathers interleave with the compute, so the
            # whole walk is one "fwd_bwd" scope (no separable gather /
            # reduce-scatter phases — that's the point of streaming).
            with jax.named_scope("fwd_bwd"):
                (loss, metrics), g = jax.value_and_grad(
                    lambda sh: _stream_loss(model, layout, sh, b),
                    has_aux=True,
                )(ps)
                if num_shards > 1:
                    g = tuple(x / num_shards for x in g)
            return loss, metrics, g
        with jax.named_scope("gather"):
            p = _materialize(layout, ps)
        with jax.named_scope("fwd_bwd"):
            (loss, metrics), grads = jax.value_and_grad(
                model.loss, has_aux=True
            )(p, b)
        with jax.named_scope("reduce_scatter"):
            g = _reduce_scatter_grads(layout, grads)
        return loss, metrics, g

    def sgd_half(ps, s, batch):
        # batch local view is (1 node, B/S, ...): strip the node dim
        b = jax.tree.map(lambda a: a[0], batch)
        loss, metrics, g = grads_of(ps, b)
        if grad_clip:
            g = _clip_sharded(g, grad_clip)
        with jax.named_scope("optimizer"):
            updates, s = opt.update(g, s, ps)
            ps = apply_updates(ps, updates)
        # per-node loss: mean of the S sub-batch token-means
        loss = jax.lax.pmean(loss, "shard")
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, "shard"), metrics)
        return ps, s, loss, metrics

    ex2 = lambda t: jax.tree.map(lambda a: a[None, None], t)

    def body(shards, opt_state, batch, bits):
        ps = tuple(a[0, 0] for a in shards)
        s = jax.tree.map(lambda a: a[0, 0], opt_state)
        if faulted:
            bits = bits[0]            # (nodes, M) -> this node's (M,) row
        ps, s, loss, metrics = sgd_half(ps, s, batch)
        if gossip_mode == "sequential":
            # masked gossip directly on the bucket shards: the ppermutes
            # run over the node axes only, so shard s exchanges with
            # shard s of the partner — 1/S of the replicated bytes per
            # matching, same arithmetic as the replicated masked mode
            with jax.named_scope("gossip"):
                ps = mix_matchings_masked(ps, alpha, perms, bits, info)
        return ex2(ps), ex2(s), loss[None, None], ex2(metrics)

    def body_overlap(shards, opt_state, gstate, batch, bits):
        ps = tuple(a[0, 0] for a in shards)
        s = jax.tree.map(lambda a: a[0, 0], opt_state)
        if faulted:
            bits = bits[0]            # (nodes, M) -> this node's (M,) row
        # 1. land the delayed correction from the in-flight exchange
        delta = tuple(a[0, 0] for a in gstate.delta)
        target = tuple(x + d for x, d in zip(ps, delta))
        ps = apply_gossip(ps, target, alpha, mesh=spec.mesh)
        # 2. launch this iteration's exchange on the corrected shards;
        #    nothing below consumes it, so the ppermutes overlap the
        #    all-gather + fwd/bwd
        recv = launch_matchings_masked(ps, bits, perms, info)
        new_delta = delayed_delta(ps, recv, bits)
        # 3. local SGD on the corrected shards
        ps, s, loss, metrics = sgd_half(ps, s, batch)
        new_state = GossipState(delta=tuple(a[None, None] for a in new_delta))
        return ex2(ps), ex2(s), new_state, loss[None, None], ex2(metrics)

    pspec = tuple(P(nodes_ax, "shard") for _ in range(layout.plan.num_buckets))
    batch_spec = P(nodes_ax, "shard")
    opt_spec = fsdp_opt_pspecs(opt, spec, layout)
    ls_spec = P(nodes_ax, "shard")
    # faulted steps take per-node (nodes, M) effective bits over the
    # node axes (replicated across "shard"); default keeps the (M,) row
    bits_spec = P(nodes_ax) if faulted else P()

    if gossip_mode == "overlap":
        gspecs = fsdp_gossip_state_pspecs(spec, layout)
        stepped = jax.shard_map(
            body_overlap,
            mesh=mesh,
            in_specs=(pspec, opt_spec, gspecs, batch_spec, bits_spec),
            out_specs=(pspec, opt_spec, gspecs, ls_spec, ls_spec),
            axis_names=manual,
            check_vma=False,
        )
        return jax.jit(stepped, donate_argnums=(0, 1, 2))

    stepped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(pspec, opt_spec, batch_spec, bits_spec),
        out_specs=(pspec, opt_spec, ls_spec, ls_spec),
        axis_names=manual,
        check_vma=False,
    )
    return jax.jit(stepped, donate_argnums=(0, 1))


def make_fsdp_gossip_flush(plan, spec: DistSpec, layout: AnyFsdpLayout):
    """Land the exchange still in flight after the last overlap step,
    directly on the shards: ``shards = flush(shards, gstate)`` — the
    sharded analogue of ``decen_train.make_gossip_flush`` (same
    ``GossipState``, same fused gossip-axpy)."""
    nodes_ax = spec.nodes_axis
    manual = set(spec.node_axes) | {"shard"}
    alpha = float(plan.alpha)

    def body(shards, gstate):
        ps = tuple(a[0, 0] for a in shards)
        delta = tuple(a[0, 0] for a in gstate.delta)
        target = tuple(x + d for x, d in zip(ps, delta))
        out = apply_gossip(ps, target, alpha, mesh=spec.mesh)
        return tuple(a[None, None] for a in out)

    pspec = tuple(P(nodes_ax, "shard") for _ in range(layout.plan.num_buckets))
    stepped = jax.shard_map(
        body,
        mesh=spec.mesh,
        in_specs=(pspec, fsdp_gossip_state_pspecs(spec, layout)),
        out_specs=pspec,
        axis_names=manual,
        check_vma=False,
    )
    return jax.jit(stepped)
