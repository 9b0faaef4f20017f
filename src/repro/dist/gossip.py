"""shard_map gossip: the MATCHA mixing step as ppermute exchanges.

One MATCHA iteration applies the mixing matrix (paper eq. 2-3)

    W^(k) = I - alpha * sum_j B_j^(k) L_j

where L_j is the Laplacian of matching j and B_j^(k) the Bernoulli
activation. Because every matching is a set of vertex-disjoint edges,
its permutation is an involution: applying W^(k) to node i's parameters
is exactly

    x_i <- x_i + alpha * sum_{active j} (x_{pi_j(i)} - x_i)

i.e. one ``ppermute`` per matching (fixed points exchange with
themselves, contributing zero) followed by a single fused elementwise
consensus update, which is routed through the Pallas gossip-axpy kernel
in ``repro.kernels.ops`` (interpret mode on CPU).

Everything here runs *inside* a ``jax.shard_map`` body whose manual
axes are the node axes (single-axis ``("data",)`` meshes or multi-pod
``("pod", "data")`` meshes — ppermute pairs index the collapsed axis in
row-major order). ``mix_dense`` is the O(m^2) oracle used by tests.

``launch_matchings_masked`` / ``delayed_delta`` are the two halves of
the overlapped (one-step-delayed) execution strategy: exchanges are
issued on contiguous fp32 buckets (``repro.dist.bucketing``) with no
consumer in the launching step, and the consensus correction lands one
iteration later — so the collective hides behind the next step's
fwd/bwd compute instead of serializing after it.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import ops

PyTree = Any

# --- static-analysis contract (consumed by repro.analysis.checks) ----------
# Every collective this module issues, with the mesh axes it may run
# over. Gossip ppermutes exchange whole replicas (or whole replica
# shards) between NODES: they run over the node axes only — a ppermute
# touching "shard" would swap slices *within* a replica and corrupt it.
COLLECTIVE_CONTRACT = {
    "ppermute": {"axes": "nodes"},       # resolved to the run's node axes
}
# Functions allowed to widen sub-fp32 values to fp32 (the consensus
# accumulation dtype). The analyzer's dtype lint flags any other fp32
# upcast traced from this file.
FP32_UPCAST_SITES = (
    "leaf",                # mix_dense: fp32-accumulated dense oracle
    "partner_target",      # mix_matchings / mix_matchings_masked deltas
    "launch_matchings_masked",
    "delayed_delta",
)


@dataclasses.dataclass(frozen=True)
class NodeAxisInfo:
    """Which mesh axes the decentralized nodes live on, and the mesh
    (see :func:`apply_gossip`)."""

    axis_names: Tuple[str, ...]
    num_nodes: int
    mesh: Optional[Mesh] = None

    @property
    def axis_name(self) -> Union[str, Tuple[str, ...]]:
        """ppermute axis arg: bare name for one axis, tuple when the
        node index is the row-major collapse of several axes."""
        if len(self.axis_names) == 1:
            return self.axis_names[0]
        return tuple(self.axis_names)


def _is_float(x) -> bool:
    return jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)


def _pairs(perm: np.ndarray) -> list:
    """(source, dest) ppermute pairs of one matching involution.

    Fixed points map to themselves so every destination is named
    exactly once (ppermute zero-fills unnamed destinations)."""
    return [(i, int(perm[i])) for i in range(len(perm))]


def _canonical_active(active: Sequence[int], num_matchings: int) -> Tuple[int, ...]:
    """Dedupe + range-check an activated-matching index set.

    Duplicate ids would double-count that matching's delta (the
    activation bits are Bernoulli, not multiplicities), and negative ids
    would silently wrap under numpy indexing — both are caller bugs, so
    dedupe the former (order-preserving) and raise on the latter."""
    out = tuple(dict.fromkeys(int(j) for j in active))
    for j in out:
        if not 0 <= j < num_matchings:
            raise ValueError(
                f"matching id {j} out of range for {num_matchings} matchings"
            )
    return out


def _check_bits(bits, num_matchings: int) -> None:
    if tuple(bits.shape) != (num_matchings,):
        raise ValueError(
            f"activation bits shape {tuple(bits.shape)} does not match the "
            f"{num_matchings} matchings in the plan"
        )


# ---------------------------------------------------------------------------
# Dense oracle
# ---------------------------------------------------------------------------
def mix_dense(stacked: PyTree, W: jax.Array) -> PyTree:
    """Apply a dense mixing matrix to node-stacked leaves: out_i = sum_j
    W[i, j] x_j (fp32 accumulation, full-precision matmul passes on
    every backend). Reference path for tests and for meshes too small
    to bother with collectives."""

    def leaf(a):
        if not _is_float(a):
            return a
        out = jnp.einsum(
            "ij,j...->i...", W.astype(jnp.float32), a.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        return out.astype(a.dtype)

    return jax.tree.map(leaf, stacked)


# ---------------------------------------------------------------------------
# shard_map matchings gossip
# ---------------------------------------------------------------------------
def apply_gossip(
    local: PyTree,
    targets: PyTree,
    alpha: float,
    *,
    mesh: Optional[Mesh] = None,
    leaf_specs: PyTree = None,
    impl: str = "auto",
) -> PyTree:
    """The consensus update ``x + alpha * (target - x)`` per leaf,
    through the fused gossip-axpy (``ops.gossip_apply``).

    The node-manual step leaves its other mesh axes (``model``) to the
    compiler, which cannot partition a Mosaic kernel. The kernel paths
    therefore run in a nested ``shard_map`` manual over those axes too.
    ``leaf_specs`` gives each leaf's PartitionSpec over them (the
    per-node param specs of the sharding rules), so a tensor-parallel
    leaf is updated shard by shard, with no gather; ``None`` means the
    leaves are replicated over them.

    ``mesh`` is the run's mesh. Under ``jax.set_mesh`` the lowering of
    a nested shard_map given only the abstract mesh swaps in the
    context's mesh, whose axes are all ``Auto``: the enclosing manual
    axes are lost and Mosaic refuses the kernel. The run's devices with
    the abstract mesh's axis types keep them. ``None`` (tests off the
    chip) uses the abstract mesh."""
    mode = ops.resolve_mode(impl, off_tpu="interpret")
    update = lambda xs, ys: ops.gossip_apply(xs, ys, alpha, impl=mode)
    here = jax.sharding.get_abstract_mesh()
    left = set(here.axis_names) - set(here.manual_axes)
    if mode == "xla" or not left:
        return update(local, targets)
    if mesh is not None:
        here = Mesh(mesh.devices, mesh.axis_names, axis_types=here.axis_types)
    specs = P() if leaf_specs is None else leaf_specs
    return jax.shard_map(
        update, mesh=here, in_specs=(specs, specs), out_specs=specs,
        axis_names=left, check_vma=False,
    )(local, targets)


def _exchange(x, axis_name, ids: Sequence[int], pair_lists) -> list:
    """``x``'s partner copies, one ``ppermute`` per matching, each under
    its own ``gossip/matching{j}`` scope. The arithmetic that combines
    them runs under ``gossip/update`` at the caller, so a device trace
    tells the exchange from the consensus update."""
    out = []
    for j, pairs in zip(ids, pair_lists):
        with jax.named_scope(f"gossip/matching{j}"):
            out.append(jax.lax.ppermute(x, axis_name, pairs))
    return out


def mix_matchings(
    local: PyTree,
    alpha: float,
    permutations: np.ndarray,            # (M, m) involutions
    active: Sequence[int],
    info: NodeAxisInfo,
    *,
    impl: str = "auto",
    gate_bits=None,                      # (M,) per-node degradation gates
    leaf_specs: PyTree = None,           # see apply_gossip
) -> PyTree:
    """Static-activation gossip: x + alpha * sum_{j in active} (pi_j(x) - x).

    ``active`` is baked into the executable (one compile per distinct
    activated subset — the "static" train-step mode).

    ``gate_bits`` (optional, traced ``(M,)`` floats in {0, 1}) is the
    fault-injection degradation path: each active matching's delta is
    scaled by this node's gate for it. The fault schedule keeps gates
    symmetric across every matching edge (``gate[u] == gate[v]``), so a
    dropped exchange degrades to self-weight renormalization — both
    endpoints keep the weight they would have sent and the effective W
    stays symmetric and doubly stochastic (``docs/fault_model.md``).
    ``None`` traces exactly today's un-gated executable."""
    active = _canonical_active(active, int(np.asarray(permutations).shape[0]))
    if not active:
        return local
    name = info.axis_name
    if gate_bits is not None:
        _check_bits(gate_bits, int(np.asarray(permutations).shape[0]))
    pair_lists = [_pairs(np.asarray(permutations[j])) for j in active]
    k = float(len(active))

    def partner_target(x):
        if not _is_float(x):
            return x
        received = _exchange(x, name, active, pair_lists)
        with jax.named_scope("gossip/update"):
            if gate_bits is None:
                acc = None
                for p in received:
                    p = p.astype(jnp.float32)
                    acc = p if acc is None else acc + p
                # y with x + alpha*(y - x) == x + alpha * sum_j (partner_j - x)
                return acc - (k - 1.0) * x.astype(jnp.float32)
            # degraded path: every active exchange still runs (same
            # collective inventory), its delta scaled by the node's gate
            xf = x.astype(jnp.float32)
            delta = jnp.zeros_like(xf)
            for j, p in zip(active, received):
                delta = delta + gate_bits[j].astype(jnp.float32) * (
                    p.astype(jnp.float32) - xf
                )
            return xf + delta

    targets = jax.tree.map(partner_target, local)
    with jax.named_scope("gossip/update"):
        return apply_gossip(local, targets, float(alpha), mesh=info.mesh,
                            leaf_specs=leaf_specs, impl=impl)


def mix_matchings_masked(
    local: PyTree,
    alpha: float,
    permutations: np.ndarray,            # (M, m) involutions
    bits: jax.Array,                     # (M,) float activation bits (traced)
    info: NodeAxisInfo,
    *,
    impl: str = "auto",
    leaf_specs: PyTree = None,           # see apply_gossip
) -> PyTree:
    """Masked gossip: every matching's exchange runs, each delta scaled
    by its (traced) activation bit — one executable for the whole
    a-priori schedule instead of one per activated subset.

    ``bits`` is this node's (M,) activation row. Fault injection reuses
    this path unchanged: the faulted step hands each node its *own*
    effective row (activation * link-survival gate, symmetric across
    every matching edge), so a dropped exchange zeroes the delta at both
    endpoints — self-weight renormalization, keeping the effective W
    symmetric and doubly stochastic (``docs/fault_model.md``)."""
    name = info.axis_name
    num = int(np.asarray(permutations).shape[0])
    _check_bits(bits, num)
    pair_lists = [_pairs(np.asarray(permutations[j])) for j in range(num)]

    def partner_target(x):
        if not _is_float(x):
            return x
        received = _exchange(x, name, range(num), pair_lists)
        with jax.named_scope("gossip/update"):
            xf = x.astype(jnp.float32)
            delta = jnp.zeros_like(xf)
            for j, p in enumerate(received):
                delta = delta + bits[j].astype(jnp.float32) * (
                    p.astype(jnp.float32) - xf
                )
            # y with x + alpha*(y - x) == x + alpha * sum_j b_j (partner_j - x)
            # (kept fp32 like the static path: rounding the target to
            # x.dtype here would make masked and static modes diverge for
            # bf16 params)
            return xf + delta

    targets = jax.tree.map(partner_target, local)
    with jax.named_scope("gossip/update"):
        return apply_gossip(local, targets, float(alpha), mesh=info.mesh,
                            leaf_specs=leaf_specs, impl=impl)


# ---------------------------------------------------------------------------
# Overlapped (one-step-delayed, bucketed) gossip
# ---------------------------------------------------------------------------
def launch_matchings_masked(
    buckets: Sequence[jax.Array],        # fp32 (B_i,) contiguous buckets
    bits: jax.Array,                     # (M,) float activation bits (traced)
    permutations: np.ndarray,            # (M, m) involutions
    info: NodeAxisInfo,
) -> Tuple[jax.Array, ...]:
    """Issue this iteration's exchanges on contiguous param buckets and
    pre-reduce the partners: recv_i = sum_j bits[j] * pi_j(bucket_i).

    This is the *launch* half of the overlap mode: nothing here feeds
    the surrounding step's loss/grad computation, so XLA's latency-hiding
    scheduler can run the ppermutes concurrently with the fwd/bwd
    matmuls traced after it. The result is consumed one step later by
    ``delayed_delta``.
    """
    name = info.axis_name
    num = int(np.asarray(permutations).shape[0])
    _check_bits(bits, num)
    pair_lists = [_pairs(np.asarray(permutations[j])) for j in range(num)]
    recv = []
    for bkt in buckets:
        acc = jnp.zeros_like(bkt)
        for j, pairs in enumerate(pair_lists):
            with jax.named_scope(f"gossip/matching{j}"):
                p = jax.lax.ppermute(bkt, name, pairs)
            acc = acc + bits[j].astype(jnp.float32) * p
        recv.append(acc)
    return tuple(recv)


def delayed_delta(
    sent: Sequence[jax.Array],           # buckets snapshotted at launch
    recv: Sequence[jax.Array],           # launch_matchings_masked output
    bits: jax.Array,                     # the bits the exchange was launched with
) -> Tuple[jax.Array, ...]:
    """Per-bucket one-step-delayed consensus delta:

        delta = sum_j b_j (pi_j(x_delayed) - x_delayed)
              = recv - (sum_j b_j) * sent

    Applying ``x <- x + alpha * delta`` (via ``apply_gossip`` with
    target ``x + delta``) is the delayed analogue of the masked mode's
    in-step correction; at consensus every pi_j(x) == x so delta == 0
    and the fixed points coincide.
    """
    ksum = jnp.sum(bits.astype(jnp.float32))
    return tuple(r - ksum * s for s, r in zip(sent, recv))
