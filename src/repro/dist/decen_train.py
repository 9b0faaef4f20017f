"""Decentralized training runtime: stacked per-node state + train step.

Layout: every node owns a full model replica, so all training state
carries a leading node dim sharded over the mesh's node axes ("data",
or ("pod", "data") multi-pod); within a node, parameters may be
tensor-parallel over "model" per the spec's rules. One train step is a
``jax.shard_map`` whose manual axes are the node axes:

    local SGD step    grads on the node's own batch shard
    gossip            ppermute matching exchanges (repro.dist.gossip)

Gossip modes (paper Section 3.3 execution strategies):
    "masked"  all matchings exchanged, deltas scaled by the (traced)
              schedule bits — ONE executable for the whole run
    "static"  the activated subset is baked in — one executable per
              distinct subset, no wasted exchanges
    "overlap" one-step-delayed bucketed gossip: iteration k's exchange
              is launched before iteration k's grads are computed and
              its consensus correction lands at iteration k+1, so the
              collective overlaps the fwd/bwd compute instead of
              serializing after it (Wang et al. 2024). Carries an
              explicit in-flight ``GossipState`` through the step.
    "none"    local SGD only (the no-communication baseline)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.dist import bucketing
from repro.dist import sharding as shd
from repro.dist.gossip import (
    NodeAxisInfo,
    apply_gossip,
    delayed_delta,
    launch_matchings_masked,
    mix_matchings,
    mix_matchings_masked,
)
from repro.optim.optimizers import Optimizer, apply_updates, clip_by_global_norm

PyTree = Any


@dataclasses.dataclass(frozen=True)
class DistSpec:
    """Mesh + node layout + sharding rules for one decentralized run."""

    mesh: Mesh
    cfg: ModelConfig
    num_nodes: int
    multi_pod: bool
    sequence_parallel: bool
    rules: shd.ShardingRules
    num_shards: int = 1           # FSDP shard factor ("shard" mesh axis)

    @property
    def node_axes(self) -> Tuple[str, ...]:
        return ("pod", "data") if self.multi_pod else ("data",)

    @property
    def nodes_axis(self):
        """The value to put in a PartitionSpec for the stacked node dim."""
        return self.rules.mapping["nodes"]

    @property
    def node_info(self) -> NodeAxisInfo:
        return NodeAxisInfo(axis_names=self.node_axes,
                            num_nodes=self.num_nodes, mesh=self.mesh)


def make_spec(
    mesh: Mesh,
    cfg: ModelConfig,
    *,
    multi_pod: bool = False,
    sequence_parallel: bool = False,
) -> DistSpec:
    """Resolve ``mesh`` + config into the runtime's `DistSpec`: node
    count and axes, shard factor, and the train-time sharding rules.

    Delegates to ``sharding.num_nodes`` — the single authority for the
    node count — which raises on a pod-axis mesh with
    ``multi_pod=False`` (that would silently gossip per-pod only)."""
    num = shd.num_nodes(mesh, multi_pod=multi_pod)
    rules = shd.train_rules(
        mesh, cfg, multi_pod=multi_pod, sequence_parallel=sequence_parallel
    )
    return DistSpec(
        mesh=mesh,
        cfg=cfg,
        num_nodes=int(num),
        multi_pod=multi_pod,
        sequence_parallel=sequence_parallel,
        rules=rules,
        num_shards=shd.num_shards(mesh),
    )


# ---------------------------------------------------------------------------
# Stacked (node-axis-leading) state
# ---------------------------------------------------------------------------
def _stack(tree: PyTree, num_nodes: int) -> PyTree:
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (num_nodes,) + a.shape), tree
    )


def init_stacked_params(model, spec: DistSpec, seed: int = 0) -> PyTree:
    """All nodes start from the same replica (standard DecenSGD init);
    divergence comes from per-node data (or an explicit perturbation)."""
    params = model.init(jax.random.key(seed))
    return _stack(params, spec.num_nodes)


def init_stacked_opt_state(opt: Optimizer, model, spec: DistSpec) -> PyTree:
    """Zero-initialized optimizer state per node: every param-shaped
    slot gains the leading ``(num_nodes,)`` dim (fp32, like the
    replicated params it mirrors)."""
    abs_local = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    zeros_local = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), abs_local)
    return _stack(opt.init(zeros_local), spec.num_nodes)


def local_param_pspecs(model, spec: DistSpec) -> PyTree:
    """Per-parameter PartitionSpecs of one node's tree: its dims per
    the model's logical axes (tensor-parallel where the rules map
    them). Inside the node-manual step these are the leaves' specs over
    the axes left to the compiler."""
    return shd.param_pspecs(model.logical_axes(), spec.rules)


def stacked_param_shardings(model, spec: DistSpec) -> PyTree:
    """Per-parameter PartitionSpecs for the stacked tree: the leading
    node dim over the node axes, the per-node dims per
    :func:`local_param_pspecs`."""
    base = local_param_pspecs(model, spec)
    nodes = spec.nodes_axis
    return jax.tree.map(
        lambda s: P(nodes, *s), base, is_leaf=lambda v: isinstance(v, P)
    )


def stacked_opt_shardings(
    opt: Optimizer, model, spec: DistSpec, pspecs: Optional[PyTree] = None
) -> PyTree:
    """Optimizer-state PartitionSpecs: param-shaped slots (velocity, mu,
    nu, ...) mirror the stacked param shardings; scalar slots (step)
    shard only over the node axis."""
    if pspecs is None:
        pspecs = stacked_param_shardings(model, spec)
    abs_local = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    state_abs = jax.eval_shape(opt.init, abs_local)
    params_struct = jax.tree.structure(abs_local)
    nodes = spec.nodes_axis
    out = {}
    for key, sub in state_abs.items():
        if jax.tree.structure(sub) == params_struct:
            out[key] = pspecs
        else:
            out[key] = jax.tree.map(lambda _: P(nodes), sub)
    return out


def consensus_distance(stacked_params: PyTree):
    """RMS-over-nodes Frobenius distance to the node mean:
    sqrt(mean_i sum_leaves ||x_i - x_bar||^2). The quantity MATCHA's
    Theorem 1 bounds; 'local' (no-gossip) training makes it blow up."""
    acc = None
    for leaf in jax.tree.leaves(stacked_params):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            continue
        x = leaf.astype(jnp.float32)
        mu = x.mean(axis=0, keepdims=True)
        d = jnp.sum((x - mu) ** 2, axis=tuple(range(1, x.ndim)))
        acc = d if acc is None else acc + d
    if acc is None:
        return jnp.float32(0.0)
    return jnp.sqrt(jnp.mean(acc))


# ---------------------------------------------------------------------------
# In-flight gossip state (overlap mode)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class GossipState:
    """The exchange in flight between two train steps (overlap mode).

    ``delta`` holds, per bucket, the pre-combined one-step-delayed
    consensus correction ``sum_j b_j (pi_j(x_delayed) - x_delayed)`` =
    ``partner_delayed - x_delayed`` terms summed over the activated
    matchings — everything the next step needs to apply
    ``x <- x + alpha * (partner_delayed - x_delayed)``. Combining at
    launch (the ppermute results must materialize before the step ends
    regardless) keeps exactly one fp32 param copy per node in flight
    instead of the send/recv pair.

    ``delta`` is one buffer per bucket of whatever bucket layout the
    run uses — byte-target buckets here (node-stacked
    ``(nodes, bucket_size)`` fp32), shard slices
    ``(nodes, S, bucket_size // S)`` in the FSDP runtime, where a
    "bucket" is either a byte-target bucket (monolithic ``FsdpLayout``)
    or one layer group (streaming ``FsdpStreamLayout``). The container
    and the flush builders are agnostic to which: they only iterate the
    tuple.
    """

    delta: Tuple[jax.Array, ...]


jax.tree_util.register_dataclass(
    GossipState, data_fields=("delta",), meta_fields=()
)


def param_bucket_plan(
    model, *, target_bytes: int = bucketing.DEFAULT_TARGET_BYTES
) -> bucketing.BucketPlan:
    """Bucket layout of one node's (un-stacked) parameter tree."""
    abs_local = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    return bucketing.plan_buckets(abs_local, target_bytes=target_bytes)


def init_gossip_state(
    plan, spec: DistSpec, bplan: bucketing.BucketPlan
) -> GossipState:
    """Empty in-flight buffer: a zero delta, so the first step's delayed
    correction is exactly zero."""
    del plan  # node/bucket layout fully determines the state
    n = spec.num_nodes
    return GossipState(
        delta=tuple(
            jnp.zeros((n, size), jnp.float32) for size in bplan.bucket_sizes
        ),
    )


def gossip_state_pspecs(spec: DistSpec, bplan: bucketing.BucketPlan) -> GossipState:
    """PartitionSpecs matching ``GossipState``: buffers shard over the
    node axes."""
    nodes = spec.nodes_axis
    return GossipState(
        delta=tuple(P(nodes) for _ in range(bplan.num_buckets))
    )


def _apply_delayed(
    p: PyTree,
    delta_buckets: Tuple[jax.Array, ...],
    bplan: bucketing.BucketPlan,
    alpha: float,
    spec: DistSpec,
    leaf_specs: PyTree,
) -> PyTree:
    """Land an in-flight delayed correction on a per-node param tree:
    ``x <- x + alpha * delta`` through the fused gossip-axpy (the one
    definition both the train step and the end-of-run flush use — they
    must stay identical for flushed checkpoints to resume exactly)."""
    with jax.named_scope("gossip/update"):
        delta_tree = bucketing.unravel(bplan, delta_buckets)
        target = jax.tree.map(
            lambda x, d: x if d is None else x.astype(jnp.float32) + d,
            p, delta_tree,
        )
        return apply_gossip(p, target, alpha, mesh=spec.mesh,
                            leaf_specs=leaf_specs)


def _reject_shard_mesh(spec: DistSpec, what: str) -> None:
    """Replicated-step builders on an FSDP mesh would silently keep a
    full O(model) copy per device (replicated over the shard axis) —
    exactly the memory blow-up the shard axis exists to remove."""
    if spec.num_shards > 1:
        raise ValueError(
            f"{what}: mesh has a 'shard' axis of size {spec.num_shards}; "
            "use the sharded-replica builders in repro.dist.fsdp"
        )


def make_gossip_flush(
    model, plan, spec: DistSpec, bplan: bucketing.BucketPlan
):
    """Land the exchange still in flight after the last overlap step:

        params = flush(params, gstate)

    Training in overlap mode leaves one delayed correction pending;
    apply it before checkpointing / evaluating consensus so the final
    replicas include every exchange the schedule paid for."""
    _reject_shard_mesh(spec, "make_gossip_flush")
    nodes_ax = spec.nodes_axis
    alpha = float(plan.alpha)
    leaf_specs = local_param_pspecs(model, spec)

    def body(params, gstate):
        p = jax.tree.map(lambda a: a[0], params)
        p = _apply_delayed(
            p, tuple(a[0] for a in gstate.delta), bplan, alpha, spec,
            leaf_specs)
        return jax.tree.map(lambda a: a[None], p)

    stepped = jax.shard_map(
        body,
        mesh=spec.mesh,
        in_specs=(P(nodes_ax), gossip_state_pspecs(spec, bplan)),
        out_specs=P(nodes_ax),
        axis_names=set(spec.node_axes),
        check_vma=False,
    )
    return jax.jit(stepped)


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------
def make_train_step(
    model,
    opt: Optimizer,
    plan,                                 # repro.core.MatchaPlan
    spec: DistSpec,
    *,
    gossip_mode: str = "masked",
    active: Sequence[int] = (),
    grad_clip: float = 0.0,
    bucket_plan: Optional[bucketing.BucketPlan] = None,
    faulted: bool = False,
):
    """Build the jitted decentralized step.

    For ``gossip_mode`` in ("masked", "static", "none"):

        params, opt_state, losses, metrics = step(params, opt_state,
                                                  batch, bits)

    For ``gossip_mode="overlap"`` the step threads the in-flight
    exchange (see ``GossipState`` / ``init_gossip_state``):

        params, opt_state, gstate, losses, metrics = step(
            params, opt_state, gstate, batch, bits)

    ``params``/``opt_state`` are node-stacked; ``batch`` leaves are
    (nodes, per_node_batch, ...); ``bits`` is the (M,) float activation
    row of the a-priori schedule (ignored for "static"/"none").
    ``losses``/``metrics`` come back per node, shape (nodes,).
    The step donates ``params``, ``opt_state`` (and ``gstate``): the
    caller must not read the arrays it passed in after the call.
    ``plan`` may be ``None`` for ``gossip_mode="none"``, which has no
    topology (a one-node run).

    ``faulted=True`` builds the link-failure-tolerant variant: ``bits``
    becomes the ``(nodes, M)`` *per-node effective* activation array
    (``repro.faults.FaultSchedule.node_bits`` — activation row times the
    step's edge-symmetric link-survival gates), sharded over the node
    axes and stripped to each node's own (M,) row inside the body. The
    gossip arithmetic is unchanged — dropped exchanges degrade to
    self-weight renormalization because both endpoints carry the same
    gate — so with all-ones gates the faulted step computes bit-identical
    results to the default one. ``faulted=False`` (default) traces
    exactly today's executable (the zero-fault parity contract).

    Overlap body order (one-step-delayed gossip, Wang et al. 2024):
    first apply the *previous* step's consensus correction
    ``x <- x + alpha * (partner_delayed - x_delayed)`` through the fused
    Pallas gossip-axpy, then snapshot the corrected params into
    contiguous fp32 buckets and launch this step's ppermutes, and only
    then trace the fwd/bwd — the collectives have no consumer inside the
    step, so XLA's latency-hiding scheduler can run them concurrently
    with the dot-products instead of after them.
    """
    if gossip_mode == "sequential":   # the fsdp-side spelling of "masked"
        gossip_mode = "masked"
    if gossip_mode not in ("masked", "static", "overlap", "none"):
        raise ValueError(f"unknown gossip_mode {gossip_mode!r}")
    _reject_shard_mesh(spec, "make_train_step")
    if plan is None and gossip_mode != "none":
        raise ValueError(f"gossip_mode {gossip_mode!r} needs a plan")
    info = spec.node_info
    leaf_specs = local_param_pspecs(model, spec)
    nodes_ax = spec.nodes_axis
    mesh = spec.mesh
    if plan is not None:
        perms = np.asarray(plan.permutations)
        alpha = float(plan.alpha)
    active = tuple(int(j) for j in active)
    if gossip_mode == "overlap":
        bplan = bucket_plan or param_bucket_plan(model)

    def sgd_half(p, s, batch):
        b = jax.tree.map(lambda a: a[0], batch)
        with jax.named_scope("fwd_bwd"):
            (loss, metrics), grads = jax.value_and_grad(
                model.loss, has_aux=True
            )(p, b)
            if grad_clip:
                grads = clip_by_global_norm(grads, grad_clip)
        with jax.named_scope("optimizer"):
            updates, s = opt.update(grads, s, p)
            p = apply_updates(p, updates)
        return p, s, loss, metrics

    expand = lambda t: jax.tree.map(lambda a: a[None], t)

    def body(params, opt_state, batch, bits):
        # strip the (local size 1) node dim: per-node trees
        p = jax.tree.map(lambda a: a[0], params)
        s = jax.tree.map(lambda a: a[0], opt_state)
        if faulted:
            bits = bits[0]            # (nodes, M) -> this node's (M,) row
        p, s, loss, metrics = sgd_half(p, s, batch)
        with jax.named_scope("gossip"):
            if gossip_mode == "masked":
                p = mix_matchings_masked(
                    p, alpha, perms, bits, info, leaf_specs=leaf_specs)
            elif gossip_mode == "static":
                p = mix_matchings(
                    p, alpha, perms, active, info,
                    gate_bits=bits if faulted else None,
                    leaf_specs=leaf_specs,
                )
        return expand(p), expand(s), loss[None], expand(metrics)

    def body_overlap(params, opt_state, gstate, batch, bits):
        p = jax.tree.map(lambda a: a[0], params)
        s = jax.tree.map(lambda a: a[0], opt_state)
        if faulted:
            bits = bits[0]            # (nodes, M) -> this node's (M,) row
        # 1. land the delayed correction from the in-flight exchange
        with jax.named_scope("gossip_apply"):
            p = _apply_delayed(
                p, tuple(a[0] for a in gstate.delta), bplan, alpha, spec,
                leaf_specs,
            )
        # 2. launch this iteration's exchange on the corrected params;
        #    the grads below don't consume it, so the collectives (and
        #    the elementwise combine into the carried delta) overlap the
        #    fwd/bwd
        with jax.named_scope("gossip_launch"):
            sent = bucketing.ravel(bplan, p)
            recv = launch_matchings_masked(sent, bits, perms, info)
            new_delta = delayed_delta(sent, recv, bits)
        # 3. local SGD on the corrected params
        p, s, loss, metrics = sgd_half(p, s, batch)
        new_state = GossipState(delta=tuple(a[None] for a in new_delta))
        return expand(p), expand(s), new_state, loss[None], expand(metrics)

    # faulted steps take per-node (nodes, M) effective bits, sharded
    # over the node axes; default steps keep the replicated (M,) row
    bits_spec = P(nodes_ax) if faulted else P()

    if gossip_mode == "overlap":
        gspecs = gossip_state_pspecs(spec, bplan)
        stepped = jax.shard_map(
            body_overlap,
            mesh=mesh,
            in_specs=(P(nodes_ax), P(nodes_ax), gspecs, P(nodes_ax),
                      bits_spec),
            out_specs=(
                P(nodes_ax), P(nodes_ax), gspecs, P(nodes_ax), P(nodes_ax),
            ),
            axis_names=set(spec.node_axes),
            check_vma=False,
        )
        return jax.jit(stepped, donate_argnums=(0, 1, 2))

    stepped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(nodes_ax), P(nodes_ax), P(nodes_ax), bits_spec),
        out_specs=(P(nodes_ax), P(nodes_ax), P(nodes_ax), P(nodes_ax)),
        axis_names=set(spec.node_axes),
        check_vma=False,
    )
    return jax.jit(stepped, donate_argnums=(0, 1))

