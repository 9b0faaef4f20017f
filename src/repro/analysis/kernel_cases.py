"""Registry-driven kernel shapes for the Pallas lint.

Every shape a kernel can see in this repo is derivable from
``repro.configs.registry``: attention gets (heads, kv heads, head_dim,
sliding window, compute dtype) from the arch config, the SSD scan gets
(heads, head_dim, state_dim, chunk), the grouped matmul gets (experts,
d_model, expert d_ff). This module turns one config into a list of
:class:`KernelCase` — a traceable callable plus abstract arguments plus
the kernel's declared contract — which ``pallas_lint.lint_case`` traces
(``jax.make_jaxpr``: nothing executes) and verifies.

Sequence lengths are fixed small (two blocks' worth, plus a ragged
variant that exercises the pad-and-mask path); block counts, not block
sizes, are what they scale, so the lint covers the same grid structure
as the full-size run at tracing cost only. ``guards`` names the masked
axes the case actually exercises, mapping the contract's masked-axis
name to the ragged bound the kernel body must guard against.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.kernels import flash_attention as _fa
from repro.kernels import gossip_axpy as _ga
from repro.kernels import grouped_matmul as _gm
from repro.kernels import ops
from repro.kernels import ssm_scan as _ss

__all__ = ["KernelCase", "cases_for_config", "shared_cases", "sweep_cases"]

# two full blocks, and a ragged length that pads up to two blocks with
# a 59-position masked tail
SEQ_ALIGNED = 256
SEQ_RAGGED = 197
BATCH = 2


@dataclasses.dataclass(frozen=True)
class KernelCase:
    label: str
    fn: object                 # callable over abstract args (traced only)
    args: Tuple                # jax.ShapeDtypeStruct operands
    contract: dict             # the kernel module's KERNEL_CONTRACT
    guards: dict               # masked-axis name -> ragged bound


def _dtype(cfg):
    return getattr(jnp, cfg.compute_dtype)


def _attention_cases(arch, preset, cfg):
    hd = cfg.head_dim
    dt = _dtype(cfg)

    def sds(s, h):
        return jax.ShapeDtypeStruct((BATCH, s, h, hd), dt)

    def case(tag, seq, window, guards):
        fn = functools.partial(
            ops.attention, causal=True, window=window, impl="pallas"
        )
        return KernelCase(
            label=f"{arch}/{preset}/flash_attention/{tag}",
            fn=fn,
            args=(
                sds(seq, cfg.num_heads),
                sds(seq, cfg.num_kv_heads),
                sds(seq, cfg.num_kv_heads),
            ),
            contract=_fa.KERNEL_CONTRACT,
            guards=guards,
        )

    out = [
        case("aligned", SEQ_ALIGNED, 0, {}),
        # ragged: ops pads 197 -> 256 and passes kv_len=197; the kernel
        # must mask k positions >= 197
        case("ragged", SEQ_RAGGED, 0, {"kv": SEQ_RAGGED}),
    ]
    if cfg.sliding_window:
        out.append(case("windowed", SEQ_ALIGNED, cfg.sliding_window, {}))
    return out


def _ssd_cases(arch, preset, cfg):
    """The SSD kernel pair: the forward alone, and forward and backward
    (the gradient traces both kernels)."""
    d_inner = cfg.ssm_expand * cfg.d_model
    P = cfg.ssm_head_dim or 64
    H = cfg.ssm_num_heads or max(1, d_inner // P)
    N = cfg.ssm_state_dim
    chunk = cfg.ssm_chunk
    S = 2 * chunk
    dt = _dtype(cfg)
    fwd = functools.partial(ops.ssd, chunk=chunk, impl="pallas")

    def fwd_bwd(*args):
        return jax.grad(
            lambda *a: jnp.sum(fwd(*a)[0].astype(jnp.float32)),
            argnums=(0, 1, 2, 3, 4))(*args)

    args = (
        jax.ShapeDtypeStruct((BATCH, S, H, P), dt),
        jax.ShapeDtypeStruct((BATCH, S, H), jnp.float32),
        jax.ShapeDtypeStruct((H,), jnp.float32),
        jax.ShapeDtypeStruct((BATCH, S, N), dt),
        jax.ShapeDtypeStruct((BATCH, S, N), dt),
    )
    return [
        KernelCase(label=f"{arch}/{preset}/ssm_scan/{tag}", fn=fn,
                   args=args, contract=_ss.KERNEL_CONTRACT, guards={})
        for tag, fn in (("aligned", fwd), ("aligned_grad", fwd_bwd))
    ]


def _gmm_cases(arch, preset, cfg):
    G = cfg.moe_num_experts
    K = cfg.d_model
    N = cfg.moe_d_ff or cfg.d_ff
    dt = _dtype(cfg)
    fn = functools.partial(ops.grouped_matmul, impl="pallas")

    def case(tag, M):
        return KernelCase(
            label=f"{arch}/{preset}/grouped_matmul/{tag}",
            fn=fn,
            args=(
                jax.ShapeDtypeStruct((M, K), dt),
                jax.ShapeDtypeStruct((G, K, N), dt),
                jax.ShapeDtypeStruct((G,), jnp.int32),
            ),
            contract=_gm.KERNEL_CONTRACT,
            # row masking via the prefetched group-offset table is
            # always active (group boundaries are data-dependent)
            guards={"rows": "scalar_prefetch"},
        )

    # ragged: 4 full row blocks + a 37-row tail padded to a 5th
    return [case("aligned", 512), case("ragged", 4 * 128 + 37)]


def _attention_only(cfg) -> bool:
    return bool(cfg.num_heads)


def cases_for_config(arch: str, preset: str, cfg) -> list:
    out = []
    if _attention_only(cfg):
        out += _attention_cases(arch, preset, cfg)
    if cfg.ssm_state_dim:
        out += _ssd_cases(arch, preset, cfg)
    if cfg.moe_num_experts:
        out += _gmm_cases(arch, preset, cfg)
    return out


def shared_cases() -> list:
    """Arch-independent gossip-axpy cases: the consensus update runs on
    raw parameter shards, so its shapes come from bucketing, not the
    model config. One aligned fp32 case, one ragged bf16 case (the
    bf16 shard must still widen to fp32 in-kernel)."""

    def fn(x, y):
        return ops.gossip_update(x, y, 0.375, impl="pallas")

    return [
        KernelCase(
            label="shared/gossip_axpy/aligned_f32",
            fn=fn,
            args=(
                jax.ShapeDtypeStruct((512, 1024), jnp.float32),
                jax.ShapeDtypeStruct((512, 1024), jnp.float32),
            ),
            contract=_ga.KERNEL_CONTRACT,
            guards={},
        ),
        KernelCase(
            label="shared/gossip_axpy/ragged_bf16",
            fn=fn,
            args=(
                jax.ShapeDtypeStruct((33, 129), jnp.bfloat16),
                jax.ShapeDtypeStruct((33, 129), jnp.bfloat16),
            ),
            contract=_ga.KERNEL_CONTRACT,
            guards={},
        ),
    ]


def sweep_cases(arch: str | None = None) -> list:
    """Every kernel case reachable from the registry.

    ``arch=None`` sweeps all registered architectures (smoke and full
    configs); an arch id restricts to that architecture. Shared gossip
    cases are always included.
    """
    archs = ARCH_IDS if arch is None else (arch,)
    out = list(shared_cases())
    for a in archs:
        for preset, cfg in (
            ("tiny", get_smoke_config(a)),
            ("full", get_config(a)),
        ):
            out += cases_for_config(a, preset, cfg)
    return out
