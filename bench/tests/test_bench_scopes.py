"""CPU tests of the per-layer readers of the model's block scopes
(``attention_ms``, ``ffn_ms``, ``ssd_ms``, ``head_ms``) on synthetic
op lists."""
import pytest

from bench_tiny import REPO  # noqa: F401  (puts the checkout on the path)

from bench import harness
from bench.metrics import (attention_ms, ffn_ms, fwd_bwd_ms, head_ms,
                           optimizer_ms, ssd_ms)
from bench.trace import Op, Trace

MS = 1e6                        # ns per ms
READERS = {"attention": attention_ms, "ffn": ffn_ms, "ssd": ssd_ms,
           "head": head_ms}


def _op(s, e, name, scope, device=0):
    return Op(device, s * MS, e * MS, name, "m", scope)


def _ctx(ops_by_device, steps=1):
    ops = {d: sorted(v, key=lambda o: (o.start, -o.end))
           for d, v in ops_by_device.items()}
    t0 = min(o.start for v in ops.values() for o in v)
    t1 = max(o.end for v in ops.values() for o in v)
    return harness.TraceContext(
        trace=Trace(ops=ops, spans=[]), window=(t0, t1),
        devices=sorted(ops), steps=steps, tokens=1, flops_per_token=1.0,
        peaks={}, chips=len(ops), replica_elements=1, bits_rows=[])


def _step():
    """One step: the layer scan (a loop under ``fwd_bwd``) around the
    blocks' forward and backward ops, the head outside it, then the
    optimizer; scope paths as the compiled step names them."""
    fwd = "jit(body)/shard_map/fwd_bwd"
    remat = fwd + "/transpose(jvp(fwd_bwd))/jvp()/checkpoint"
    return [
        _op(0, 100, "while.1", fwd + "/while"),
        _op(5, 15, "fusion.1", fwd + "/jvp()/checkpoint/attention/dot"),
        _op(15, 25, "fusion.2", fwd + "/jvp()/checkpoint/ffn/dot"),
        _op(25, 30, "fusion.3", fwd + "/jvp()/checkpoint/norm/mul"),
        _op(40, 60, "fusion.4", remat + "/attention/dot_general"),
        _op(60, 90, "fusion.5", remat + "/ffn/dot_general"),
        _op(100, 107, "fusion.6", fwd + "/jvp(head)/log"),
        _op(107, 111, "fusion.7", fwd + "/transpose(jvp(head))/dot"),
        _op(111, 120, "fusion.8", "jit(body)/shard_map/optimizer/add"),
    ]


def test_blocks_take_their_self_time_in_fwd_and_bwd():
    ctx = _ctx({0: _step()})
    got = {b: r.read(ctx) for b, r in READERS.items()}
    assert got["attention"] == pytest.approx(10 + 20)
    assert got["ffn"] == pytest.approx(10 + 30)
    assert got["head"] == pytest.approx(7 + 4)
    assert got["ssd"] is None               # a dense model has no scan
    # the loop's own time and the norm stay in fwd_bwd, outside the blocks
    fwd = fwd_bwd_ms.read(ctx)
    assert fwd == pytest.approx(111)
    assert sum(v for v in got.values() if v) == pytest.approx(fwd - 30)
    assert optimizer_ms.read(ctx) == pytest.approx(9)


def test_ssd_scan_inside_the_remat_body():
    fwd = "jit(body)/shard_map/fwd_bwd"
    body = fwd + "/transpose(jvp(fwd_bwd))/jvp()/checkpoint/rematted_computation"
    ops = [_op(0, 50, "while.2", fwd + "/while"),
           _op(5, 25, "fusion.9", body + "/ssd/bcijh,bcjhp->bcihp/dot_general"),
           _op(25, 40, "fusion.10", body + "/mixer/conv"),
           _op(50, 60, "fusion.11", fwd + "/jvp(head)/log")]
    ctx = _ctx({0: ops}, steps=2)
    assert ssd_ms.read(ctx) == pytest.approx(20 / 2)
    assert head_ms.read(ctx) == pytest.approx(10 / 2)
    assert attention_ms.read(ctx) is None


def test_blocks_count_only_under_fwd_bwd():
    """A block scope outside the training step (a serving prefill) and
    an op whose path merely contains the word are not counted."""
    ops = [_op(0, 10, "fusion.1", "jit(prefill)/attention/dot"),
           _op(10, 20, "fusion.2", "jit(body)/fwd_bwd/attention_mask/add"),
           _op(20, 30, "fusion.3", "jit(body)/gossip/gossip/update/add")]
    ctx = _ctx({0: ops})
    assert all(r.read(ctx) is None for r in READERS.values())


def test_blocks_read_the_longest_chip():
    scope = "jit(body)/fwd_bwd/jvp()/checkpoint/ffn/dot"
    ctx = _ctx({0: [_op(0, 10, "fusion.1", scope)],
                1: [_op(0, 25, "fusion.1", scope, device=1)]})
    assert ffn_ms.read(ctx) == pytest.approx(25)


def test_silent_on_a_step_without_the_block_scopes():
    """The parent program marks only ``fwd_bwd``: every block reads
    nothing."""
    ops = [_op(0, 50, "fusion.1", "jit(body)/fwd_bwd/dot"),
           _op(50, 60, "fusion.2", "jit(body)/optimizer/add")]
    ctx = _ctx({0: ops})
    assert all(r.read(ctx) is None for r in READERS.values())
    assert fwd_bwd_ms.read(ctx) == pytest.approx(50)
