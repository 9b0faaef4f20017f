"""Jit'd public wrappers around the Pallas kernels.

Dispatch policy: on TPU backends the Pallas kernels run compiled; on CPU
(this container) they run in interpret mode for correctness tests, and
the model code uses the jnp reference paths for anything that must
*lower* on CPU (the multi-pod dry-run). ``impl="auto"`` resolves that
choice per backend via :func:`resolve_mode` — the ONE place the
backend/interpret decision is made; the kernels themselves take the
resolved ``interpret`` flag and carry no default (a hardcoded
``interpret=`` outside this module is a lint violation, see
``repro.analysis.pallas_lint.check_interpret_literals``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import gossip_axpy as _ga
from repro.kernels import grouped_matmul as _gm
from repro.kernels import ssm_scan as _ss
from repro.kernels import ref as _ref

MODES = ("xla", "pallas", "interpret")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_mode(impl: str, *, off_tpu: str = "xla") -> str:
    """Resolve an ``impl`` string to an execution mode.

    ``"auto"`` resolves to ``"pallas"`` on TPU and to ``off_tpu``
    elsewhere (``"xla"`` for the model-facing wrappers, ``"interpret"``
    for the gossip hot path, which must exercise the kernel on every
    backend). Explicit modes pass through unchanged — in particular
    ``"pallas"`` now forces the *compiled* kernel even off-TPU (useful
    for tracing/lowering studies; it will fail to lower on CPU, which
    is the point). Unknown strings raise instead of silently falling
    through to a kernel path they never selected.
    """
    if impl == "auto":
        return "pallas" if _on_tpu() else off_tpu
    if impl not in MODES:
        raise ValueError(
            f"unknown impl/mode {impl!r}: expected 'auto' or one of {MODES}"
        )
    return impl


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------
@functools.partial(
    jax.jit, static_argnames=("causal", "window", "impl", "block_q", "block_k")
)
def attention(
    q, k, v, *, causal: bool = True, window: int = 0,
    impl: str = "auto", block_q: int = 128, block_k: int = 128,
):
    mode = resolve_mode(impl)
    if mode == "xla":
        return _ref.attention_ref(q, k, v, causal=causal, window=window)
    Sq, Sk = q.shape[1], k.shape[1]
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    pad_q = (-Sq) % bq
    pad_k = (-Sk) % bk
    if pad_q or pad_k:
        # pad q/k/v up to block multiples; padded queries are sliced off
        # below and padded keys are masked inside the kernel via kv_len
        # (causal masking alone only hides them for self-attention —
        # with causal=False or a window they would leak exp(0) mass
        # into the softmax denominator)
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    out = _fa.flash_attention(
        q, k, v, causal=causal, window=window,
        kv_len=Sk if pad_k else 0,
        block_q=bq, block_k=bk, interpret=mode == "interpret",
    )
    return out[:, :Sq] if pad_q else out


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("chunk", "impl"))
def ssd(
    x, dt, A, B_mat, C_mat, *, chunk: int = 128, h0=None,
    impl: str = "auto",
):
    """``ssd_chunked(..., return_final_state=True)``: (y, final state).
    The chunk is halved until it divides the sequence."""
    from repro.models.ssm import ssd_chunked

    mode = resolve_mode(impl)
    S = x.shape[1]
    c = min(chunk, S)
    while S % c:
        c //= 2
    if mode == "xla":
        return ssd_chunked(x, dt, A, B_mat, C_mat, chunk=c, h0=h0,
                           return_final_state=True)
    return _ss.ssd(x, dt, A, B_mat, C_mat, chunk=c, h0=h0,
                   interpret=mode == "interpret")


# ---------------------------------------------------------------------------
# Gossip consensus update
# ---------------------------------------------------------------------------
def _gossip_tree_map(x_tree, partner_tree, alpha: float, mode: str):
    """Shared leaf dispatcher for the consensus update x + alpha*(y - x).
    Non-float leaves pass through untouched. ``mode`` is already
    resolved (one of :data:`MODES`). The kernel paths need a region
    manual over every mesh axis (the compiler cannot partition a Mosaic
    kernel); ``repro.dist.gossip.apply_gossip`` provides it."""

    def leaf(x, y):
        if not jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
            return x
        if mode == "xla":
            return _ref.gossip_axpy_ref(x, y, alpha)
        return _ga.gossip_axpy(x, y, alpha, interpret=mode == "interpret")

    return jax.tree.map(leaf, x_tree, partner_tree)


def gossip_update(x_tree, partner_tree, alpha: float, *, impl: str = "auto"):
    """Tree-wide fused consensus update x + alpha (partner - x)."""
    return _gossip_tree_map(x_tree, partner_tree, alpha, resolve_mode(impl))


def gossip_apply(x_tree, target_tree, alpha: float, *, impl: str = "auto"):
    """Gossip HOT-PATH entry used by ``repro.dist.gossip`` after the
    ppermute exchanges.

    Unlike ``gossip_update`` (whose "auto" falls back to the jnp
    reference off-TPU), the hot path always runs the fused Pallas
    gossip-axpy — compiled on TPU, interpreted on CPU — so the kernel
    is exercised by every decentralized train step and stays validated
    against ``repro.kernels.ref.gossip_axpy_ref`` in situ. Pass
    ``impl="xla"`` to force the reference path.
    """
    return _gossip_tree_map(
        x_tree, target_tree, alpha, resolve_mode(impl, off_tpu="interpret")
    )


# ---------------------------------------------------------------------------
# Grouped matmul (MoE expert compute, megablox-lite)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("impl", "block_m", "block_n"))
def grouped_matmul(x, w, group_sizes, *, impl: str = "auto",
                   block_m: int = 128, block_n: int = 128):
    mode = resolve_mode(impl)
    if mode == "xla":
        return _ref.grouped_matmul_ref(x, w, group_sizes)
    return _gm.grouped_matmul(
        x, w, group_sizes, block_m=block_m, block_n=block_n,
        interpret=mode == "interpret",
    )
