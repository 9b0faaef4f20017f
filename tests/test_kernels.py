"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.gossip_axpy import gossip_axpy
from repro.kernels.grouped_matmul import grouped_matmul
from repro.kernels import ssm_scan
from repro.kernels import ops
from repro.models.ssm import ssd_chunked
from repro.kernels.ref import (
    attention_ref, gossip_axpy_ref, grouped_matmul_ref, ssm_scan_ref,
)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(
        atol=2e-5, rtol=2e-5
    )


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,Hq,Hkv,hd,bq,bk",
    [
        (1, 128, 4, 4, 64, 64, 64),     # MHA
        (2, 256, 8, 2, 64, 128, 64),    # GQA 4:1
        (1, 192, 6, 1, 32, 64, 64),     # MQA, ragged grid
        (2, 64, 4, 4, 128, 32, 32),     # wide heads
    ],
)
def test_flash_attention_sweep(B, S, Hq, Hkv, hd, bq, bk, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), dtype)
    for causal, window in [(True, 0), (True, S // 4), (False, 0)]:
        got = flash_attention(
            q, k, v, causal=causal, window=window,
            block_q=bq, block_k=bk, interpret=True,
        )
        want = attention_ref(q, k, v, causal=causal, window=window)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            **_tol(dtype),
        )


def test_flash_attention_padding_wrapper():
    """ops.attention pads ragged seq lens to block multiples."""
    ks = jax.random.split(jax.random.key(1), 3)
    B, S, H, hd = 1, 100, 4, 64        # 100 does not divide 64
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    got = ops.attention(q, k, v, causal=True, impl="interpret", block_q=64, block_k=64)
    want = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal,window", [(False, 0), (False, 24), (True, 24)])
@pytest.mark.parametrize("Sq,Sk", [(100, 100), (64, 100), (37, 130)])
def test_flash_attention_pad_masking_parity(Sq, Sk, causal, window):
    """Padded K/V positions must carry zero softmax mass.

    With causal=False (and with window set) only an explicit kv_len
    mask hides the pad — exp(0)=1 leaks into the denominator otherwise.
    Non-multiple-of-block lengths force the padded path."""
    ks = jax.random.split(jax.random.key(3), 3)
    B, H, hd = 2, 4, 32
    q = jax.random.normal(ks[0], (B, Sq, H, hd))
    k = jax.random.normal(ks[1], (B, Sk, H, hd))
    v = jax.random.normal(ks[2], (B, Sk, H, hd))
    got = ops.attention(q, k, v, causal=causal, window=window,
                        impl="interpret", block_q=64, block_k=64)
    want = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_kv_len_rejects_bad_range():
    q = jnp.zeros((1, 64, 2, 32))
    with pytest.raises(ValueError, match="kv_len"):
        flash_attention(q, q, q, kv_len=65, interpret=True)


def test_flash_attention_fully_masked_rows_are_finite():
    """window smaller than block: early rows of late blocks fully masked."""
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 32))
    k = jax.random.normal(ks[1], (1, 128, 2, 32))
    v = jax.random.normal(ks[2], (1, 128, 2, 32))
    out = flash_attention(q, k, v, causal=True, window=8, block_q=32, block_k=32,
                          interpret=True)
    assert bool(jnp.isfinite(out).all())


# ---------------------------------------------------------------------------
# SSD scan: the kernel pair (forward and backward) behind ops.ssd
# ---------------------------------------------------------------------------
def _ssd_inputs(B, S, H, P, N, dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    x = (jax.random.normal(ks[0], (B, S, H, P)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(dtype)
    A = -jnp.exp(jax.random.uniform(ks[2], (H,)))
    Bm = (jax.random.normal(ks[3], (B, S, N)) * 0.3).astype(dtype)
    Cm = (jax.random.normal(ks[4], (B, S, N)) * 0.3).astype(dtype)
    h0 = (jax.random.normal(ks[5], (B, H, N, P)) * 0.3).astype(dtype)
    return (x, dt, A, Bm, Cm), h0


def _ssd_vjp(fn, args, h0, seed=1):
    """(y, final state) and the gradients of every input (h0 included
    where given) under random cotangents."""
    out, vjp = jax.vjp(fn, *args, h0) if h0 is not None else jax.vjp(
        lambda *a: fn(*a, None), *args)
    ks = jax.random.split(jax.random.key(seed), 2)
    cts = tuple(jax.random.normal(k, o.shape).astype(o.dtype)
                for k, o in zip(ks, out))
    return list(out) + list(vjp(cts))


def _kernel(chunk):
    def fn(x, dt, A, Bm, Cm, h0):
        return ssm_scan.ssd(x, dt, A, Bm, Cm, chunk=chunk, h0=h0,
                            interpret=True)
    return fn


def _chunked(chunk):
    def fn(x, dt, A, Bm, Cm, h0):
        return ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk, h0=h0,
                           return_final_state=True)
    return fn


def _oracle(x, dt, A, Bm, Cm, h0):
    return ssm_scan_ref(x, dt, A, Bm, Cm, h0=h0)


def _assert_rel(got, want, rtol, what):
    """Relative error in norm. dA sums dt * d(log-decay) over every
    position and both signs: in f32 the sequential oracle and
    ``ssd_chunked`` differ there by 2.3e-4 (full-size state, 2 chunks),
    so f32 comparisons of dA get ten times the room."""
    if "dA" in what and rtol < 1e-3:
        rtol = 1e-3
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)
    assert err <= rtol, (what, err)


SSD_NAMES = ("y", "h", "dx", "ddt", "dA", "dB", "dC", "dh0")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,P,N,chunk",
    [
        (1, 64, 2, 16, 8, 16),
        (2, 128, 4, 32, 16, 32),
        (1, 256, 2, 64, 128, 128),     # full-size state dims
        (2, 96, 3, 16, 8, 32),         # nc = 3
        (2, 64, 2, 16, 8, 64),         # nc = 1
        (1, 384, 2, 64, 128, 128),     # full-size state dims, nc = 3
    ],
)
def test_ssm_scan_sweep(B, S, H, P, N, chunk, dtype):
    """The kernel pair against the sequential oracle (y and the final
    state) and ``ssd_chunked``, the path it replaces (y, the final state
    and the gradients of x, dt, A, B, C and h0), with and without an
    initial state. The MXU takes bf16 operands where the inputs are
    bf16, so bf16 cases compare at bf16's precision."""
    args, h0 = _ssd_inputs(B, S, H, P, N, dtype)
    y, h = ssm_scan.ssd(*args, chunk=chunk, interpret=True)
    y_ref, h_ref = ssm_scan_ref(*args)
    tol = dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else dict(
        atol=1e-4, rtol=1e-3
    )
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32), **tol
    )
    np.testing.assert_allclose(
        np.asarray(h, np.float32), np.asarray(h_ref, np.float32), **tol
    )
    rtol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    for init in (None, h0):
        got = _ssd_vjp(_kernel(chunk), args, init)
        want = _ssd_vjp(_chunked(chunk), args, init)
        assert got[0].dtype == want[0].dtype == dtype
        assert got[1].dtype == want[1].dtype
        for name, g, w in zip(SSD_NAMES, got, want):
            _assert_rel(g, w, rtol, (name, init is not None))


@pytest.mark.parametrize("with_h0", [False, True])
def test_ssm_scan_gradients_match_sequential_oracle(with_h0):
    """Kernel gradients == gradients of the step-by-step recurrence."""
    args, h0 = _ssd_inputs(2, 96, 3, 16, 8, jnp.float32, seed=3)
    init = h0 if with_h0 else None
    got = _ssd_vjp(_kernel(32), args, init)
    want = _ssd_vjp(_oracle, args, init)
    for name, g, w in zip(SSD_NAMES, got, want):
        _assert_rel(g, w, 1e-4, name)


def test_ssm_scan_matches_chunked_model_path():
    """Kernel == models.ssm.ssd_chunked == sequential oracle, forward
    and backward, from an initial state."""
    args, h0 = _ssd_inputs(2, 128, 2, 16, 8, jnp.float32, seed=7)
    k = _ssd_vjp(_kernel(32), args, h0)
    c = _ssd_vjp(_chunked(32), args, h0)
    o = _ssd_vjp(_oracle, args, h0)
    for name, a, b, r in zip(SSD_NAMES, k, c, o):
        _assert_rel(a, b, 1e-4, name)
        _assert_rel(a, r, 1e-4, name)


def test_ssm_scan_tiles_only_aligned_blocks():
    """The compiled kernel's shape rule: mamba2-370m (32 heads of 64)
    takes one block of all heads; jamba's 128 heads take blocks of 32;
    shapes that do not tile keep the jnp path."""
    assert ssm_scan.head_block(32, 64) == 32
    assert ssm_scan.head_block(128, 64) == 32
    assert ssm_scan.head_block(8, 32) == 8
    assert ssm_scan.head_block(4, 24) is None      # part of a row tile
    assert ssm_scan.tiles(2048, 32, 64, 128)
    assert ssm_scan.tiles(32, 8, 32, 32)           # one chunk
    assert not ssm_scan.tiles(1, 32, 64, 1)        # decode
    assert not ssm_scan.tiles(2048, 32, 64, 64)    # chunk off the lanes
    assert not ssm_scan.tiles(100, 32, 64, 4)
    assert not ssm_scan.tiles(2048, 4, 24, 128)


def test_mamba_block_takes_the_kernel_where_it_tiles(monkeypatch):
    """With ``auto`` resolved to a kernel mode (as on the chip), the
    Mamba2 block's scan runs the kernel pair, and its output and
    parameter gradients are those of the jnp path."""
    import dataclasses

    from repro.analysis.pallas_lint import find_pallas_calls
    from repro.configs.registry import get_smoke_config
    from repro.models import ssm
    from repro.models.module import ParamBuilder

    cfg = dataclasses.replace(get_smoke_config("mamba2_370m"),
                              compute_dtype="float32")
    b = ParamBuilder()
    ssm.declare_mamba(b, "m", cfg)
    params = b.init(jax.random.key(0))["m"]
    x = jax.random.normal(jax.random.key(1), (2, 64, cfg.d_model))

    def run():
        def loss(p):      # a new function each time: traces are cached
            out, _ = ssm.mamba_block(p, x, cfg)
            return jnp.sum(jnp.sin(out))

        kernels = find_pallas_calls(jax.make_jaxpr(loss)(params))
        return jax.value_and_grad(loss)(params), len(kernels)

    (want, want_g), kernels = run()
    assert kernels == 0
    resolve = ops.resolve_mode
    monkeypatch.setattr(ops, "resolve_mode", lambda impl, off_tpu="xla": (
        resolve("interpret" if impl == "auto" else impl, off_tpu=off_tpu)))
    (got, got_g), kernels = run()
    assert kernels == 1
    _assert_rel(got, want, 1e-5, "loss")
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got_g),
                            jax.tree.leaves(want_g)):
        _assert_rel(g, w, 1e-4, jax.tree_util.keystr(path))


# ---------------------------------------------------------------------------
# gossip axpy
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "shape", [(17,), (1003, 77), (4, 33, 9), (2048, 1024)]
)
@pytest.mark.parametrize("alpha", [0.0, 0.3, 1.0])
def test_gossip_axpy_sweep(shape, alpha, dtype):
    ks = jax.random.split(jax.random.key(0), 2)
    x = jax.random.normal(ks[0], shape).astype(dtype)
    y = jax.random.normal(ks[1], shape).astype(dtype)
    got = gossip_axpy(x, y, alpha, interpret=True)
    want = gossip_axpy_ref(x, y, alpha)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


def test_gossip_update_tree():
    tree_x = {"a": jnp.ones((64, 64)), "b": {"c": jnp.zeros((130,))}}
    tree_y = {"a": jnp.zeros((64, 64)), "b": {"c": jnp.ones((130,))}}
    out = ops.gossip_update(tree_x, tree_y, 0.25, impl="interpret")
    assert float(out["a"][0, 0]) == pytest.approx(0.75)
    assert float(out["b"]["c"][0]) == pytest.approx(0.25)


# ---------------------------------------------------------------------------
# impl resolution (ops.resolve_mode): the ONE dispatch decision point
# ---------------------------------------------------------------------------
def test_resolve_mode_auto_and_passthrough():
    assert jax.default_backend() != "tpu"   # this container is CPU-only
    # "auto" resolves per backend: reference path for model wrappers,
    # interpreted kernel for the gossip hot path
    assert ops.resolve_mode("auto") == "xla"
    assert ops.resolve_mode("auto", off_tpu="interpret") == "interpret"
    # explicit modes pass through unchanged (including "pallas", which
    # now means the compiled kernel even off-TPU)
    for mode in ops.MODES:
        assert ops.resolve_mode(mode) == mode


def test_resolve_mode_rejects_unknown_impl():
    for bad in ("fused", "", "Pallas", "interp"):
        with pytest.raises(ValueError, match="unknown impl"):
            ops.resolve_mode(bad)


# ---------------------------------------------------------------------------
# wrapper-level tail parity: interpret vs reference on ragged shapes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("M", [37, 165])
def test_grouped_matmul_wrapper_tail_parity(M):
    """Row counts that don't divide the block: the interpreted kernel
    and the jnp reference must agree through the public wrapper."""
    ks = jax.random.split(jax.random.key(M), 2)
    G, K, N = 4, 32, 48
    x = jax.random.normal(ks[0], (M, K))
    w = jax.random.normal(ks[1], (G, K, N)) * 0.2
    rng = np.random.default_rng(M)
    cuts = np.sort(rng.choice(M, G - 1, replace=False))
    sizes = jnp.asarray(
        np.diff(np.concatenate([[0], cuts, [M]])), jnp.int32
    )
    got = ops.grouped_matmul(x, w, sizes, impl="interpret")
    want = ops.grouped_matmul(x, w, sizes, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("S", [100, 52])
def test_ssd_wrapper_tail_parity(S):
    """Sequence lengths that don't divide the chunk: ops.ssd halves the
    chunk until it divides; kernel output must still match the
    reference scan."""
    ks = jax.random.split(jax.random.key(S), 5)
    B, H, P, N = 2, 2, 16, 8
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, N)) * 0.3
    y, h = ops.ssd(x, dt, A, Bm, Cm, chunk=64, impl="interpret")
    y_ref, h_ref = ops.ssd(x, dt, A, Bm, Cm, chunk=64, impl="xla")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# grouped matmul (megablox-lite)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "M,K,N,G,bm,bn",
    [
        (96, 32, 48, 4, 32, 32),
        (256, 64, 128, 8, 128, 64),
        (130, 16, 40, 3, 32, 32),      # ragged tail blocks
        (64, 128, 256, 16, 32, 128),   # many groups, some empty
    ],
)
def test_grouped_matmul_sweep(M, K, N, G, bm, bn, dtype):
    ks = jax.random.split(jax.random.key(0), 2)
    x = jax.random.normal(ks[0], (M, K)).astype(dtype)
    w = (jax.random.normal(ks[1], (G, K, N)) * 0.2).astype(dtype)
    rng = np.random.default_rng(M + G)
    cuts = np.sort(rng.choice(M, G - 1, replace=False))
    sizes = np.diff(np.concatenate([[0], cuts, [M]])).astype(np.int32)
    got = grouped_matmul(x, w, jnp.asarray(sizes), block_m=bm, block_n=bn,
                         interpret=True)
    want = grouped_matmul_ref(x, w, jnp.asarray(sizes))
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tol(dtype),
    )


def test_grouped_matmul_empty_groups():
    """Zero-size groups are skipped without corrupting neighbours."""
    x = jax.random.normal(jax.random.key(1), (64, 16))
    w = jax.random.normal(jax.random.key(2), (4, 16, 24)) * 0.3
    sizes = jnp.asarray([0, 40, 0, 24], jnp.int32)
    got = grouped_matmul(x, w, sizes, block_m=32, block_n=24, interpret=True)
    want = grouped_matmul_ref(x, w, sizes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
