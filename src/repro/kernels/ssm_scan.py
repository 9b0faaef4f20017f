"""Mamba2 SSD chunk scan: a trainable Pallas TPU kernel pair.

``ssd`` has the signature and results of
``repro.models.ssm.ssd_chunked(..., return_final_state=True)`` and a
``jax.custom_vjp``: one kernel evaluates the forward, one the backward.
Both run the grid (batch, head block, chunk) with the chunk axis last,
which the TPU walks sequentially: the forward carries the state
forward through the chunks, the backward (whose index maps walk the
chunks in reverse) carries its gradient back. The per-head (Q, Q)
decay-weighted matrix, the f32 copies of the inputs, the intra and
inter terms and the carried state live only in VMEM; HBM sees the
inputs, the outputs and one residual, the state at every chunk start.

Per chunk and head, with La the chunk-local cumsum of dt * A:

    intra:  y_i  = sum_{j<=i} (C_i.B_j) exp(La_i - La_j) dt_j x_j
    inter:  y_i += exp(La_i) C_i . h_in
    state:  h_out = exp(La_Q) h_in + sum_j exp(La_Q - La_j) dt_j B_j (x) x_j

The kernels see every sequence transposed, positions on the lanes:
``x``, ``y`` and their gradients as (B, H*P, S), ``B`` and ``C`` as
(B, N, S), ``dt`` as (B, H, S), the state as (H*P, N). A head is then P
whole rows, and the Mamba block's activations, which XLA lays out with
the sequence minor, reach the kernels with no copy. Only the intra term
needs a (Q, Q) matrix per head; ``C.B^T`` is shared by the heads
(ngroups 1), and the inter and state terms are one matmul each for the
block of heads. Per-head rows spread over their P rows by a broadcast,
and per-head sums over P are sums over rows.

``exp(La_i - La_j)`` is taken of the difference, never factored: La
reaches about -200 over a chunk. Cumsums, exponents, the decay mask,
the carried state and every accumulation are f32. The MXU gets the
model's operands in the dtype of ``x``: for bf16 inputs that is what
the f32 einsums of ``ssd_chunked`` get on TPU at ``Precision.DEFAULT``
(one bf16 pass). The cumsums over the chunk are matmuls with a 0/1
triangle at ``Precision.HIGHEST``.

TARGET: TPU. Validated on CPU via interpret=True against
``ssd_chunked`` and the sequential oracle; the execution mode is
resolved by ``repro.kernels.ops.resolve_mode`` and threaded in (no
default here).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The carried state, its gradient and every accumulation are fp32
# whatever the operand dtype (the decays underflow in bf16).
ACC_DTYPE = jnp.float32
# rows of x per grid step: the head block is the most heads that fit
MAX_BLOCK_ROWS = 2048
# what the compiler may take of VMEM for one grid step: the pipelined
# blocks (about 9 MiB for mamba2-370m's backward) and the temporaries
COMPILER_VMEM_BYTES = 64 * 2**20

# See flash_attention.KERNEL_CONTRACT for the field semantics. Both
# kernels share it. No masked axes: the dispatch takes the kernel only
# where S % chunk == 0. The final state (forward) and the gradient of
# the initial state (backward) are written once, on the last step of
# the sequential chunk axis, so that axis is the reduction axis.
KERNEL_CONTRACT = dict(
    kernel="ssm_scan",
    grid=("batch", "head_block", "chunk"),
    reduction_axes=(2,),
    masked={},
    acc_dtype="float32",
    vmem_limit_bytes=16 * 2**20,
)

_NN = (((1,), (0,)), ((), ()))     # a @ b
_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_TN = (((0,), (0,)), ((), ()))     # a.T @ b


def head_block(H: int, P: int) -> Optional[int]:
    """Heads per grid step: the most that divide ``H`` in at most
    ``MAX_BLOCK_ROWS`` rows and give the (B, H, S) rows a legal block (a
    multiple of 8 heads, or all of them). None where none does, or
    where a head is not a whole number of bf16 row tiles (16)."""
    if P % 16:
        return None
    for hb in range(H, 0, -1):
        if (H % hb == 0 and hb * P <= MAX_BLOCK_ROWS
                and (hb % 8 == 0 or hb == H)):
            return hb
    return None


def tiles(S: int, H: int, P: int, chunk: int) -> bool:
    """Whether the compiled kernel takes these shapes: more than one
    position, whole chunks of a lane-aligned length (or one chunk), and
    a head block."""
    return (S > 1 and S % chunk == 0
            and (chunk % 128 == 0 or chunk == S)
            and head_block(H, P) is not None)


# Index maps are module-level named functions so the static analyzer's
# mutation tests can patch them. ``c`` is the grid's chunk step; the
# backward's maps walk the chunks in reverse (``_rev``).
def seq_index_map(b, g, c):
    return (b, g, c)


def bc_index_map(b, g, c):
    return (b, 0, c)


def a_index_map(b, g, c):
    return (g, 0)


def state_index_map(b, g, c):
    return (b, g, 0)


def starts_index_map(b, g, c):
    return (b, c, g, 0)


def grad_bc_index_map(b, g, c):
    return (b, g, 0, c)


def _rev(nc: int, index_map):
    def rev(b, g, c):
        return index_map(b, g, nc - 1 - c)
    return rev


def _dot(a, b, dims, dtype):
    """A matmul of the model's operands, in ``dtype``, f32 accumulation."""
    return jax.lax.dot_general(
        a.astype(dtype), b.astype(dtype), dims,
        preferred_element_type=ACC_DTYPE)


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _cumsum(rows, reverse=False):
    """Cumsum along the lanes of (hb, Q) f32 rows: a matmul with a 0/1
    triangle at full precision."""
    q = rows.shape[1]
    k, i = _iota((q, q), 0), _iota((q, q), 1)
    tri = (k >= i if reverse else k <= i).astype(ACC_DTYPE)
    return jax.lax.dot_general(
        rows, tri, _NN, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=ACC_DTYPE)


class _Chunk:
    """What the heads of one grid step share: La and dt per head as rows
    (hb, Q) and La as columns (Q, hb), the causal mask, and the spread of
    per-head values over their heads' P rows."""

    def __init__(self, dt_ref, a_ref, head_dim: int):
        self.P = head_dim
        self.dt_r = dt_ref[0]                              # (hb, Q)
        hb, q = self.dt_r.shape
        self.cum_r = _cumsum(self.dt_r * a_ref[...])       # La
        self.cum_c = self.cum_r.T                          # (Q, hb)
        self.causal = _iota((q, q), 0) >= _iota((q, q), 1)
        last = self.cum_r[:, q - 1:]                       # (hb, 1) La_Q
        self.tail_r = jnp.exp(last - self.cum_r)           # exp(La_Q - La_j)
        self.w_r = self.tail_r * self.dt_r                 # state weights
        self.decay_q = self.rows(jnp.exp(last))            # (hb*P, 1)

    def rows(self, per_head):
        """(hb, n) per-head values -> (hb * P, n), each over its P rows."""
        hb, n = per_head.shape
        full = jnp.broadcast_to(per_head[:, None, :], (hb, self.P, n))
        return full.reshape(hb * self.P, n)

    def per_head(self, full):
        """(hb * P, n) -> (hb, n): the sum over each head's P rows."""
        n = full.shape[1]
        return jnp.sum(full.reshape(-1, self.P, n), axis=1)

    def head(self, h):
        return slice(h * self.P, (h + 1) * self.P)

    def decay(self, h):
        """exp(La_i - La_j) for j <= i, else 0, of head ``h``: (Q, Q)."""
        diff = self.cum_c[:, h:h + 1] - self.cum_r[h:h + 1]
        return jnp.exp(jnp.where(self.causal, diff, -jnp.inf))


def _fwd_kernel(*refs, head_dim: int, has_h0: bool):
    if has_h0:
        (x_ref, b_ref, c_ref, dt_ref, a_ref, h0_ref,
         y_ref, hend_ref, hs_ref, h_scr) = refs
    else:
        (x_ref, b_ref, c_ref, dt_ref, a_ref,
         y_ref, hend_ref, hs_ref, h_scr) = refs
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        if has_h0:
            h_scr[...] = h0_ref[0].astype(ACC_DTYPE)
        else:
            h_scr[...] = jnp.zeros_like(h_scr)

    mm = x_ref.dtype
    ch = _Chunk(dt_ref, a_ref, head_dim)
    hb = ch.dt_r.shape[0]
    x = x_ref[0]                                      # (hb*P, Q)
    bt = b_ref[0]                                     # (N, Q)
    ct = c_ref[0]
    h_in = h_scr[...]                                 # (hb*P, N)
    hs_ref[0, 0] = h_in
    cb = _dot(ct, bt, _TN, mm)                        # (Q, Q) C_i . B_j
    # inter: y_i += exp(La_i) C_i . h_in, every head at once
    y = ch.rows(jnp.exp(ch.cum_r)) * _dot(h_in, ct, _NN, mm)
    for h in range(hb):
        rows = ch.head(h)
        m = cb * ch.decay(h) * ch.dt_r[h:h + 1]
        y_ref[0, rows] = (y[rows] + _dot(x[rows], m, _NT, mm)).astype(
            y_ref.dtype)
    xw = x.astype(ACC_DTYPE) * ch.rows(ch.w_r)
    h_out = ch.decay_q * h_in + _dot(xw, bt, _NT, mm)
    h_scr[...] = h_out

    @pl.when(ic == pl.num_programs(2) - 1)
    def _emit():
        hend_ref[0] = h_out.astype(hend_ref.dtype)


def _bwd_kernel(*refs, head_dim: int, has_h0: bool):
    (x_ref, b_ref, c_ref, dt_ref, a_ref, dy_ref, hs_ref, dhend_ref,
     dx_ref, db_ref, dc_ref, dloga_ref, ddt_ref, *rest) = refs
    if has_h0:
        dh0_ref, dh_scr = rest
    else:
        (dh_scr,) = rest
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        dh_scr[...] = dhend_ref[0].astype(ACC_DTYPE)

    f32 = ACC_DTYPE
    mm = x_ref.dtype
    ch = _Chunk(dt_ref, a_ref, head_dim)
    hb, q = ch.dt_r.shape
    x = x_ref[0]                                      # (hb*P, Q)
    dy = dy_ref[0]
    xf = x.astype(f32)
    bt = b_ref[0]                                     # (N, Q)
    ct = c_ref[0]
    h_in = hs_ref[0, 0]                               # (hb*P, N) chunk start
    d_out = dh_scr[...]                               # (hb*P, N) d h_out

    # inter: y_i += exp(La_i) C_i . h_in
    e = jnp.exp(ch.cum_r)                             # (hb, Q)
    edy = ch.rows(e) * dy.astype(f32)
    dcum = e * ch.per_head(dy.astype(f32) * _dot(h_in, ct, _NN, mm))
    dct = _dot(h_in, edy, _TN, mm)                    # (N, Q)
    dh_scr[...] = _dot(edy, ct, _NT, mm) + ch.decay_q * d_out
    # state: h_out = exp(La_Q) h_in + sum_j w_j B_j (x) x_j
    u = _dot(d_out, bt, _NN, mm)                      # (hb*P, Q)
    dx = ch.rows(ch.w_r) * u
    dbt = _dot(d_out, xf * ch.rows(ch.w_r), _TN, mm)  # (N, Q)
    k = ch.per_head(xf * u)                           # d w_j, (hb, Q)
    wk = ch.w_r * k
    dlast = (jnp.sum(ch.per_head(ch.decay_q * d_out * h_in), axis=1,
                     keepdims=True)
             + jnp.sum(wk, axis=1, keepdims=True))    # (hb, 1) d La_Q
    dcum += jnp.where(_iota((hb, q), 1) == q - 1, dlast, 0.0) - wk
    ddt = ch.tail_r * k

    # intra: y = M x with M = C.B^T * decay * dt_j, per head
    cb = _dot(ct, bt, _TN, mm)
    dcb = jnp.zeros((q, q), f32)
    row_h = _iota((hb, q), 0)
    for h in range(hb):
        rows = ch.head(h)
        dr = ch.dt_r[h:h + 1]
        decay = ch.decay(h)
        cbl = cb * decay
        g = _dot(dy[rows], x[rows], _TN, mm)          # (Q, Q) dy_i . x_j
        dx_ref[0, rows] = (dx[rows] + _dot(dy[rows], cbl * dr, _NN, mm)
                           ).astype(dx_ref.dtype)
        gd = g * decay
        gl = gd * cb
        dcb += gd * dr
        ddt_h = jnp.sum(gl, axis=0, keepdims=True)    # over i
        dcum_h = jnp.sum((gl * dr).T, axis=0, keepdims=True)  # over j
        dcum += jnp.where(row_h == h, dcum_h - ddt_h * dr, 0.0)
        ddt += jnp.where(row_h == h, ddt_h, 0.0)
    dc_ref[0, 0] = dct + _dot(bt, dcb, _NT, mm)
    db_ref[0, 0] = dbt + _dot(ct, dcb, _NN, mm)
    # La = cumsum(dt * A) within the chunk: its reverse
    dloga_ref[0] = _cumsum(dcum, reverse=True)
    ddt_ref[0] = ddt

    if has_h0:
        @pl.when(ic == pl.num_programs(2) - 1)
        def _emit():
            dh0_ref[0] = dh_scr[...].astype(dh0_ref.dtype)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=COMPILER_VMEM_BYTES)


def _seq_rows(a):
    """(B, S, ...) -> (B, prod(...), S): positions on the lanes."""
    return jnp.swapaxes(a.reshape(a.shape[0], a.shape[1], -1), 1, 2)


def _state_rows(h):
    """(B, H, N, P) -> (B, H*P, N): the kernels' state layout."""
    Bsz, H, N, P = h.shape
    return jnp.swapaxes(h, 2, 3).reshape(Bsz, H * P, N)


def _state_heads(h, H):
    """(B, H*P, N) -> (B, H, N, P)."""
    Bsz, L, N = h.shape
    return jnp.swapaxes(h.reshape(Bsz, H, L // H, N), 2, 3)


def _shared(x, dt, A, B_mat, C_mat, chunk, index):
    """Operands and block specs of x, B, C, dt and A, which both kernels
    read, and the head block."""
    Bsz, S, H, P = x.shape
    N = B_mat.shape[-1]
    hb = head_block(H, P) or H
    args = [_seq_rows(x), _seq_rows(B_mat), _seq_rows(C_mat),
            _seq_rows(dt.astype(ACC_DTYPE)),
            A.astype(ACC_DTYPE).reshape(H, 1)]
    specs = [
        pl.BlockSpec((1, hb * P, chunk), index(seq_index_map)),
        pl.BlockSpec((1, N, chunk), index(bc_index_map)),
        pl.BlockSpec((1, N, chunk), index(bc_index_map)),
        pl.BlockSpec((1, hb, chunk), index(seq_index_map)),
        pl.BlockSpec((hb, 1), a_index_map),
    ]
    return args, specs, hb


def _forward(x, dt, A, B_mat, C_mat, h0, chunk, interpret):
    Bsz, S, H, P = x.shape
    N = B_mat.shape[-1]
    nc = S // chunk
    args, in_specs, hb = _shared(x, dt, A, B_mat, C_mat, chunk,
                                 lambda f: f)
    L = hb * P
    state = pl.BlockSpec((1, L, N), state_index_map)
    if h0 is not None:
        in_specs.append(state)
        args.append(_state_rows(h0))
    kernel = functools.partial(_fwd_kernel, head_dim=P,
                               has_h0=h0 is not None)
    y, h_end, h_starts = pl.pallas_call(
        kernel,
        grid=(Bsz, H // hb, nc),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, L, chunk), seq_index_map),
            state,
            pl.BlockSpec((1, 1, L, N), starts_index_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, H * P, S), x.dtype),
            jax.ShapeDtypeStruct((Bsz, H * P, N), x.dtype),
            jax.ShapeDtypeStruct((Bsz, nc, H * P, N), ACC_DTYPE),
        ],
        scratch_shapes=[pltpu.VMEM((L, N), ACC_DTYPE)],
        compiler_params=_params(),
        interpret=interpret,
    )(*args)
    y = jnp.swapaxes(y, 1, 2).reshape(Bsz, S, H, P)
    return (y, _state_heads(h_end, H)), h_starts


def _backward(x, dt, A, B_mat, C_mat, h_starts, dy, dh_end, has_h0,
              chunk, interpret):
    Bsz, S, H, P = x.shape
    N = B_mat.shape[-1]
    nc = S // chunk
    rev = functools.partial(_rev, nc)
    args, in_specs, hb = _shared(x, dt, A, B_mat, C_mat, chunk, rev)
    G, L = H // hb, hb * P
    seq = pl.BlockSpec((1, L, chunk), rev(seq_index_map))
    rows = pl.BlockSpec((1, hb, chunk), rev(seq_index_map))
    state = pl.BlockSpec((1, L, N), state_index_map)
    grad_bc = pl.BlockSpec((1, 1, N, chunk), rev(grad_bc_index_map))
    out_specs = [seq, grad_bc, grad_bc, rows, rows]
    out_shape = [
        jax.ShapeDtypeStruct((Bsz, H * P, S), x.dtype),
        jax.ShapeDtypeStruct((Bsz, G, N, S), ACC_DTYPE),
        jax.ShapeDtypeStruct((Bsz, G, N, S), ACC_DTYPE),
        jax.ShapeDtypeStruct((Bsz, H, S), ACC_DTYPE),
        jax.ShapeDtypeStruct((Bsz, H, S), ACC_DTYPE),
    ]
    if has_h0:
        out_specs.append(state)
        out_shape.append(jax.ShapeDtypeStruct((Bsz, H * P, N), ACC_DTYPE))
    kernel = functools.partial(_bwd_kernel, head_dim=P, has_h0=has_h0)
    outs = pl.pallas_call(
        kernel,
        grid=(Bsz, G, nc),
        in_specs=in_specs + [
            seq,
            pl.BlockSpec((1, 1, L, N), rev(starts_index_map)),
            state,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((L, N), ACC_DTYPE)],
        compiler_params=_params(),
        interpret=interpret,
    )(*args, _seq_rows(dy), h_starts,
      _state_rows(dh_end.astype(ACC_DTYPE)))
    dx, dB, dC, dloga, ddt = outs[:5]
    # the log-decay dt * A splits its gradient between dt and A
    dt_r, Af = args[3], args[4]
    ddt = jnp.swapaxes(ddt + Af * dloga, 1, 2)
    dA = jnp.sum(dt_r * dloga, axis=(0, 2))
    dh0 = _state_heads(outs[5], H) if has_h0 else None
    return (jnp.swapaxes(dx, 1, 2).reshape(Bsz, S, H, P), ddt, dA,
            jnp.swapaxes(jnp.sum(dB, axis=1), 1, 2),
            jnp.swapaxes(jnp.sum(dC, axis=1), 1, 2), dh0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd(x, dt, A, B_mat, C_mat, h0, chunk, interpret):
    return _forward(x, dt, A, B_mat, C_mat, h0, chunk, interpret)[0]


def _ssd_fwd(x, dt, A, B_mat, C_mat, h0, chunk, interpret):
    out, h_starts = _forward(x, dt, A, B_mat, C_mat, h0, chunk, interpret)
    return out, (x, dt, A, B_mat, C_mat, h_starts, h0)


def _ssd_bwd(chunk, interpret, res, cts):
    x, dt, A, B_mat, C_mat, h_starts, h0 = res
    dy, dh_end = cts
    dx, ddt, dA, dB, dC, dh0 = _backward(
        x, dt, A, B_mat, C_mat, h_starts, dy, dh_end, h0 is not None,
        chunk, interpret)
    return (dx, ddt.astype(dt.dtype), dA.astype(A.dtype),
            dB.astype(B_mat.dtype), dC.astype(C_mat.dtype),
            None if h0 is None else dh0.astype(h0.dtype))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(
    x: jax.Array,        # (B, S, H, P)
    dt: jax.Array,       # (B, S, H), positive
    A: jax.Array,        # (H,), negative
    B_mat: jax.Array,    # (B, S, N)
    C_mat: jax.Array,    # (B, S, N)
    *,
    chunk: int,
    h0: Optional[jax.Array] = None,   # (B, H, N, P)
    interpret: bool,
):
    """Returns (y (B, S, H, P) in x's dtype, final state (B, H, N, P) in
    x's dtype), as ``ssd_chunked`` does; differentiable in every array
    argument. ``S`` must be a multiple of ``chunk``."""
    if x.shape[1] % chunk:
        raise ValueError(f"seq {x.shape[1]} not divisible by chunk {chunk}")
    return _ssd(x, dt, A, B_mat, C_mat, h0, chunk, interpret)
