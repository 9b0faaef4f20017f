"""Mamba2 (SSD — state-space duality) blocks. [arXiv:2405.21060]

Selective state space with scalar-per-head decay:

    h_t = exp(dt_t * A) h_{t-1} + dt_t * (B_t  (x)  x_t)        (N x P state)
    y_t = C_t . h_t + D * x_t

Three execution paths:
  * sequential lax.scan over time — the oracle (exact recurrence), used
    for decode (one step) and in ref tests;
  * chunked SSD (this file): intra-chunk attention-like masked matmul +
    inter-chunk state scan. O(S Q) instead of O(S^2); the train/prefill
    path off the chip and for shapes the kernel does not tile;
  * the Pallas kernel pair (repro.kernels.ssm_scan, forward and
    backward), the same chunked evaluation with its intermediates in
    VMEM: ``mamba_block`` takes it on TPU where the shapes tile.

Sharding: heads are tensor-parallel ("heads"); B/C projections are
per-group (ngroups=1) and replicated; the state (B, H, N, P) shards over
heads, so the recurrence is collective-free within a node.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax._src.mesh import get_concrete_mesh
from jax.sharding import Mesh, PartitionSpec

from repro.configs.base import ModelConfig
from repro.dist.sharding import shard
from repro.kernels import ops, ssm_scan
from repro.models.layers import apply_dense, declare_dense
from repro.models.module import ParamBuilder, ones_init, zeros_init


def ssm_dims(cfg: ModelConfig) -> dict:
    d_inner = cfg.ssm_expand * cfg.d_model
    head_dim = cfg.ssm_head_dim or 64
    nheads = cfg.ssm_num_heads or d_inner // head_dim
    return dict(
        d_inner=d_inner,
        head_dim=head_dim,
        nheads=nheads,
        dstate=cfg.ssm_state_dim,
        conv_width=cfg.ssm_conv_width,
        conv_dim=d_inner + 2 * cfg.ssm_state_dim,   # x, B, C are conv'd
    )


# ---------------------------------------------------------------------------
# Declarations
# ---------------------------------------------------------------------------
def declare_mamba(b: ParamBuilder, path: str, cfg: ModelConfig) -> None:
    d = cfg.d_model
    dims = ssm_dims(cfg)
    di, H, N = dims["d_inner"], dims["nheads"], dims["dstate"]
    declare_dense(b, f"{path}.in_z", d, di, (None, "ssm_inner"))
    declare_dense(b, f"{path}.in_x", d, di, (None, "ssm_inner"))
    declare_dense(b, f"{path}.in_b", d, N, (None, None))
    declare_dense(b, f"{path}.in_c", d, N, (None, None))
    declare_dense(b, f"{path}.in_dt", d, H, (None, "ssm_heads"))
    b.declare(f"{path}.conv_w", (dims["conv_width"], dims["conv_dim"]),
              (None, None), init=_conv_init)
    b.declare(f"{path}.conv_b", (dims["conv_dim"],), (None,), init=zeros_init)
    b.declare(f"{path}.A_log", (H,), ("ssm_heads",), init=_a_log_init)
    b.declare(f"{path}.D", (H,), ("ssm_heads",), init=ones_init)
    b.declare(f"{path}.dt_bias", (H,), ("ssm_heads",), init=_dt_bias_init)
    b.declare(f"{path}.norm_scale", (di,), ("ssm_inner",), init=ones_init)
    declare_dense(b, f"{path}.out", di, d, ("ssm_inner", None))


def _a_log_init(key, shape, dtype):
    # A in [1, 16] as in mamba2 reference init
    a = jax.random.uniform(key, shape, minval=1.0, maxval=16.0)
    return jnp.log(a).astype(dtype)


def _dt_bias_init(key, shape, dtype):
    # dt in [1e-3, 1e-1] through softplus
    dt = jnp.exp(
        jax.random.uniform(key, shape)
        * (np.log(1e-1) - np.log(1e-3))
        + np.log(1e-3)
    )
    return jnp.log(jnp.expm1(dt)).astype(dtype)


def _conv_init(key, shape, dtype):
    scale = 1.0 / np.sqrt(shape[0])
    return (jax.random.uniform(key, shape, minval=-scale, maxval=scale)).astype(dtype)


# ---------------------------------------------------------------------------
# Chunked SSD core (pure jnp; kernels/ssm_scan.py evaluates it on TPU)
# ---------------------------------------------------------------------------
def ssd_chunked(
    x: jax.Array,        # (B, S, H, P)
    dt: jax.Array,       # (B, S, H) — post-softplus, positive
    A: jax.Array,        # (H,) negative decay rates
    B_mat: jax.Array,    # (B, S, N)
    C_mat: jax.Array,    # (B, S, N)
    *,
    chunk: int,
    h0: Optional[jax.Array] = None,   # (B, H, N, P) initial state
    return_final_state: bool = False,
):
    """Exact SSD recurrence evaluated chunk-parallel.

    Returns y (B,S,H,P) [and final state (B,H,N,P)].
    """
    Bsz, S, H, P = x.shape
    N = B_mat.shape[-1]
    if S % chunk:
        raise ValueError(f"seq {S} not divisible by chunk {chunk}")
    nc = S // chunk
    f32 = jnp.float32

    xc = x.reshape(Bsz, nc, chunk, H, P).astype(f32)
    dtc = dt.reshape(Bsz, nc, chunk, H).astype(f32)
    Bc = B_mat.reshape(Bsz, nc, chunk, N).astype(f32)
    Cc = C_mat.reshape(Bsz, nc, chunk, N).astype(f32)

    loga = dtc * A.astype(f32)[None, None, None, :]          # (B,nc,Q,H) <= 0
    cum = jnp.cumsum(loga, axis=2)                           # La_i
    # intra-chunk: M_ij = (C_i . B_j) exp(La_i - La_j) dt_j, j <= i
    CB = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)               # (B,nc,Q,Q)
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]     # (B,nc,Q,Q,H)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(mask[None, None, :, :, None], diff, -jnp.inf))
    M = CB[..., None] * decay * dtc[:, :, None, :, :]        # (B,nc,Q,Q,H)
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", M, xc)

    # chunk-final states: S_c = sum_j exp(La_Q - La_j) dt_j B_j (x) x_j
    tail = jnp.exp(cum[:, :, -1:, :] - cum) * dtc            # (B,nc,Q,H)
    chunk_state = jnp.einsum("bcjh,bcjn,bcjhp->bchnp", tail, Bc, xc)
    chunk_decay = jnp.exp(cum[:, :, -1, :])                  # (B,nc,H)

    # inter-chunk scan over nc (the only sequential part)
    def scan_fn(h, inp):
        st, dec = inp                                        # (B,H,N,P),(B,H)
        h_new = h * dec[..., None, None] + st
        return h_new, h

    init = (
        jnp.zeros((Bsz, H, N, P), f32)
        if h0 is None
        else h0.astype(f32)
    )
    cs = jnp.moveaxis(chunk_state, 1, 0)                     # (nc,B,H,N,P)
    cd = jnp.moveaxis(chunk_decay, 1, 0)                     # (nc,B,H)
    h_final, h_starts = jax.lax.scan(scan_fn, init, (cs, cd))
    h_starts = jnp.moveaxis(h_starts, 0, 1)                  # (B,nc,H,N,P)

    # inter-chunk contribution: y_i += C_i . (exp(La_i) h_start)
    inter = jnp.einsum(
        "bcin,bchnp,bcih->bcihp",
        Cc,
        h_starts,
        jnp.exp(cum),
    )
    y = (y_intra + inter).reshape(Bsz, S, H, P)
    if return_final_state:
        return y.astype(x.dtype), h_final.astype(x.dtype)
    return y.astype(x.dtype)


def ssd_sequential(
    x: jax.Array, dt: jax.Array, A: jax.Array,
    B_mat: jax.Array, C_mat: jax.Array,
    *, h0: Optional[jax.Array] = None, return_final_state: bool = False,
):
    """Step-by-step oracle recurrence (used in tests and decode)."""
    Bsz, S, H, P = x.shape
    N = B_mat.shape[-1]
    f32 = jnp.float32
    init = jnp.zeros((Bsz, H, N, P), f32) if h0 is None else h0.astype(f32)

    def step(h, inp):
        xt, dtt, bt, ct = inp
        a = jnp.exp(dtt.astype(f32) * A.astype(f32))         # (B,H)
        hb = jnp.einsum("bh,bn,bhp->bhnp", dtt.astype(f32), bt.astype(f32),
                        xt.astype(f32))
        h = h * a[..., None, None] + hb
        y = jnp.einsum("bn,bhnp->bhp", ct.astype(f32), h)
        return h, y

    xs = (
        jnp.moveaxis(x, 1, 0),
        jnp.moveaxis(dt, 1, 0),
        jnp.moveaxis(B_mat, 1, 0),
        jnp.moveaxis(C_mat, 1, 0),
    )
    h_final, ys = jax.lax.scan(step, init, xs)
    y = jnp.moveaxis(ys, 0, 1).astype(x.dtype)
    if return_final_state:
        return y, h_final.astype(x.dtype)
    return y


# ---------------------------------------------------------------------------
# Causal conv1d helper (width-w depthwise)
# ---------------------------------------------------------------------------
def causal_conv1d(
    u: jax.Array,            # (B, S, C)
    w: jax.Array,            # (W, C)
    bias: jax.Array,         # (C,)
    state: Optional[jax.Array] = None,   # (B, W-1, C) carried for decode
) -> Tuple[jax.Array, jax.Array]:
    W = w.shape[0]
    if state is None:
        state = jnp.zeros((u.shape[0], W - 1, u.shape[-1]), u.dtype)
    padded = jnp.concatenate([state.astype(u.dtype), u], axis=1)
    out = sum(
        padded[:, i : i + u.shape[1], :] * w[i][None, None, :]
        for i in range(W)
    )
    out = out + bias[None, None, :]
    new_state = padded[:, -(W - 1) :, :]
    return jax.nn.silu(out), new_state


def _manual_region(fn):
    """``fn`` in a region manual over every mesh axis, as a Mosaic
    kernel needs: the compiler cannot partition one. None where that
    would split the kernel's operands (an axis left to the compiler
    spans several devices: tensor parallel) or where no mesh set by
    ``jax.set_mesh`` names the devices of such axes. As in
    ``dist.gossip.apply_gossip``, the region takes the run's devices
    with the abstract mesh's axis types, so the enclosing manual axes
    stay manual; ``jax.sharding.get_mesh`` refuses to run under ``jit``,
    so the mesh is read from where ``jax.set_mesh`` keeps it."""
    here = jax.sharding.get_abstract_mesh()
    left = set(here.axis_names) - set(here.manual_axes)
    if not left:
        return fn
    mesh = get_concrete_mesh()
    if mesh.empty or any(here.shape[a] > 1 for a in left):
        return None
    mesh = Mesh(mesh.devices, mesh.axis_names, axis_types=here.axis_types)
    return jax.shard_map(fn, mesh=mesh, in_specs=PartitionSpec(),
                         out_specs=PartitionSpec(), axis_names=left,
                         check_vma=False)


# ---------------------------------------------------------------------------
# Full Mamba2 block
# ---------------------------------------------------------------------------
def mamba_block(
    p: dict,
    x: jax.Array,                       # (B, S, D)
    cfg: ModelConfig,
    *,
    state: Optional[dict] = None,       # {"ssm": (B,H,N,P), "conv": (B,W-1,Cd)}
    return_state: bool = False,
) -> Tuple[jax.Array, Optional[dict]]:
    dtype = jnp.dtype(cfg.compute_dtype)
    dims = ssm_dims(cfg)
    H, P, N = dims["nheads"], dims["head_dim"], dims["dstate"]
    Bsz, S, _ = x.shape

    z = apply_dense(p["in_z"], x, dtype)                     # (B,S,di)
    xs = apply_dense(p["in_x"], x, dtype)
    bs = apply_dense(p["in_b"], x, dtype)                    # (B,S,N)
    cs = apply_dense(p["in_c"], x, dtype)
    dt_raw = apply_dense(p["in_dt"], x, dtype)               # (B,S,H)

    conv_in = jnp.concatenate([xs, bs, cs], axis=-1)
    conv_state = None if state is None else state["conv"]
    conv_out, new_conv_state = causal_conv1d(
        conv_in, p["conv_w"].astype(dtype), p["conv_b"].astype(dtype),
        conv_state,
    )
    di = dims["d_inner"]
    xs = conv_out[..., :di]
    bs = conv_out[..., di : di + N]
    cs = conv_out[..., di + N :]

    dt = jax.nn.softplus(
        dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32)
    )
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    xh = xs.reshape(Bsz, S, H, P)
    xh = shard(xh, ("batch", "seq", "ssm_heads", None))

    h0 = None if state is None else state["ssm"]
    with jax.named_scope("ssd"):
        if S == 1:
            y, h_final = ssd_sequential(
                xh, dt, A, bs, cs, h0=h0, return_final_state=True
            )
        else:
            chunk = min(cfg.ssm_chunk, S)
            while S % chunk:
                chunk //= 2
            mode = ops.resolve_mode("auto")

            def scan(x, dt, A, B_mat, C_mat, h0):
                return ops.ssd(x, dt, A, B_mat, C_mat, chunk=chunk, h0=h0,
                               impl=mode)

            kernel = None
            if mode != "xla" and ssm_scan.tiles(S, H, P, chunk):
                kernel = _manual_region(scan)
            if kernel is not None:
                with jax.named_scope("kernel"):
                    y, h_final = kernel(xh, dt, A, bs, cs, h0)
            else:
                y, h_final = ssd_chunked(
                    xh, dt, A, bs, cs, chunk=chunk, h0=h0,
                    return_final_state=True,
                )
    y = y + xh * p["D"].astype(jnp.float32)[None, None, :, None].astype(y.dtype)
    y = y.reshape(Bsz, S, di)
    # gated RMSNorm (mamba2): norm(y * silu(z)); fp32 statistics only
    y = (y * jax.nn.silu(z)).astype(dtype)
    yf = y.astype(jnp.float32)
    stat = jax.lax.rsqrt(jnp.mean(yf * yf, axis=-1, keepdims=True) + 1e-6)
    y = y * stat.astype(dtype) * p["norm_scale"].astype(dtype)
    out = apply_dense(p["out"], y, dtype)
    out = shard(out, ("batch", "seq", "embed"))
    if return_state:
        return out, {"ssm": h_final, "conv": new_conv_state}
    return out, None


def init_mamba_state(batch: int, cfg: ModelConfig, dtype) -> dict:
    dims = ssm_dims(cfg)
    return {
        "ssm": jnp.zeros(
            (batch, dims["nheads"], dims["dstate"], dims["head_dim"]), dtype
        ),
        "conv": jnp.zeros(
            (batch, dims["conv_width"] - 1, dims["conv_dim"]), dtype
        ),
    }
