"""CPU tests of the benchmark's arithmetic and of how it finds a cell."""
import importlib
import json
import math

import numpy as np
import pytest

from bench_tiny import REPO, make_root

from bench import check, harness, run
from bench.flops import dense, ssm
from bench.metrics import consensus_update_roofline
from bench.trace import (Op, exclusive_ns, gaps, in_scope, self_time_ns,
                         union_ns)

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
INTERNLM2 = json.loads(
    (REPO / "bench/configs/internlm2-1.8b-8l.json").read_text())
MAMBA2 = json.loads((REPO / "bench/configs/mamba2-370m.json").read_text())


# -- FLOPs at the published widths, counted by hand -----------------------
def test_internlm2_flops_per_token_by_hand():
    cfg = dict(INTERNLM2, num_hidden_layers=24)
    # q 2048x2048, k and v 2048x1024 each, o 2048x2048, ffn 3 x 2048x8192
    per_layer = 4194304 + 2 * 2097152 + 4194304 + 3 * 16777216
    head = 2048 * 92544
    assert dense.matmul_params(cfg) == 24 * per_layer + head == 1699479552
    # causal attention: 6 L H D (S + 1) per token over a 4096 sequence
    assert dense.flops_per_token(cfg, 4096) == (
        6 * 1699479552 + 6 * 24 * 16 * 128 * 4097)


def test_mamba2_flops_per_token_by_hand():
    # in_z, in_x 1024x2048; in_b, in_c 1024x128; in_dt 1024x32; out 2048x1024
    per_layer = 2 * 2097152 + 2 * 131072 + 32768 + 2097152
    head = 1024 * 50280
    assert ssm.matmul_params(MAMBA2) == 48 * per_layer + head == 367632384
    scan = 3 * 4 * 32 * 128 * 64          # 32 heads, state 128, head 64
    conv = 3 * 2 * 4 * (2048 + 256)
    assert ssm.flops_per_token(MAMBA2, 2048) == (
        6 * 367632384 + 48 * (scan + conv))


# -- the consensus update's bytes -----------------------------------------
def test_consensus_update_bytes_count_the_algorithm():
    n = 1000
    rows = [[0, 0, 0], [1, 0, 0], [1, 1, 1], [0, 1, 0]]
    # nothing on a step with no activation; x, k partners and the write
    want = 4 * n * ((2 + 1) + (2 + 3) + (2 + 1))
    assert consensus_update_roofline.needed_bytes(n, rows) == want
    assert consensus_update_roofline.needed_bytes(n, [[0, 0, 0]]) == 0


def test_roofline_is_silent_without_work():
    class Ctx:
        replica_elements, bits_rows, steps = 10, [[0, 0, 0]], 1
        peaks = {"hbm_bytes_per_s": 1e9}

        def scope_ms(self, pred):
            return 5.0

    assert consensus_update_roofline.read(Ctx()) is None


# -- p90 over all steps ---------------------------------------------------
def test_step_samples_group_consecutive_steps():
    # 100 ms steps: groups of 3 span the least host-clock time a sample
    # may (0.25 s); a last partial group is left out
    t0 = 10.0
    completions = [10.1, 10.2, 10.3, 10.45, 10.55, 10.65, 10.75]
    samples, per = harness.step_samples(t0, completions)
    assert per == 3
    assert samples == pytest.approx([100.0, 350.0 / 3])


def test_step_samples_are_single_steps_when_steps_are_long():
    # 0.5 s steps: every gap between completions is a sample, so a
    # single heavy step is seen whole
    t0 = 0.0
    completions = [0.5, 1.0, 2.5, 3.0]
    samples, per = harness.step_samples(t0, completions)
    assert per == 1
    assert samples == pytest.approx([500.0, 500.0, 1500.0, 500.0])
    assert harness.slowest_steps(t0, completions, 1) == [
        [2, pytest.approx(1500.0)]]


def test_p90_over_all_samples():
    values = list(range(1, 101))
    assert harness.p90(values) == pytest.approx(90.1)
    assert harness.p90([5.0] * 20 + [100.0] * 3) == pytest.approx(81.0)
    with pytest.raises(ValueError):
        harness.p90([1.0])


# -- trace reduction on interval lists ------------------------------------
def _op(s, e, name="fusion", scope=""):
    return Op(0, float(s), float(e), name, "m", scope)


def test_union_and_gaps():
    ops = [_op(0, 10), _op(5, 20), _op(30, 40)]
    assert union_ns(ops) == 30
    assert gaps(ops, 0, 50) == [(20, 30), (40, 50)]


def test_self_time_counts_nested_ops_once():
    loop = _op(0, 100, "while", "jit(f)/fwd_bwd")
    body = [_op(10, 40, "fusion.1", "jit(f)/transpose(jvp(fwd_bwd))/dot"),
            _op(50, 60, "fusion.2", "jit(f)/optimizer/add")]
    ops = [loop] + body
    fwd = self_time_ns(ops, lambda o: in_scope(o.scope, "fwd_bwd"))
    opt = self_time_ns(ops, lambda o: in_scope(o.scope, "optimizer"))
    assert (fwd, opt) == (90, 10)


def test_exposed_collective_time():
    coll = lambda o: o.name.startswith("collective-permute")  # noqa: E731
    ops = [_op(0, 100, "while"),
           _op(10, 30, "collective-permute-done.1", "gossip/matching0"),
           _op(20, 25, "fusion.3"),             # compute overlaps 5 ns
           _op(40, 50, "collective-permute-done.2", "gossip/matching1")]
    assert exclusive_ns(ops, coll) == 15 + 10


def test_scope_match_under_transforms():
    assert in_scope("jit(step)/shard_map/gossip/matching2/ppermute",
                    "gossip/matching*")
    assert in_scope("jit(step)/transpose(jvp(fwd_bwd))/dot", "fwd_bwd")
    assert not in_scope("jit(step)/gossip_apply/add", "gossip")


# -- every piece found by name --------------------------------------------
@pytest.mark.parametrize("workload", [w["name"] for w in MANIFEST["workloads"]])
def test_every_cell_loads_by_name(workload):
    cell = harness.load_cell(workload)
    assert cell.config["name"] == cell.workload["config"]
    assert cell.traffic["name"] == cell.workload["traffic"]
    assert harness.flops_per_token(cell) > 0
    importlib.import_module(f"bench.reference.{cell.config['family']}")
    body = json.loads(
        (REPO / "bench" / "limits" / f"{workload}.json").read_text())
    compared = set(body["limits"])
    # every number is compared, or named as not compared with its readings
    assert compared and compared <= set(check.NAMES)
    assert set(check.NAMES) - compared == set(body.get("not_compared", []))
    assert {m["name"] for m in cell.end_to_end} >= {"tokens_per_s",
                                                   "setup_s"}
    assert cell.per_layer


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_every_metric_has_its_reader(metric):
    assert callable(harness.metric_reader(metric).read)


def test_every_config_file_lists_its_cut():
    for c in MANIFEST["configs"]:
        body = json.loads((REPO / c["file"]).read_text())
        assert body["name"] == c["name"]
        assert body["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert key in body["published"]
        assert body["source"] == c["source"]


def test_a_new_cell_needs_only_files_and_entries(tmp_path):
    bench = make_root(tmp_path, "dense")
    cell = harness.load_cell("tiny", bench)
    assert cell.config["name"] == "tiny-dense"
    assert cell.traffic["name"] == "tiny-mix"
    assert cell.tokens_per_step == 2 * 64
    assert harness.flops_per_token(cell) > 0


# -- the run refuses the CPU ----------------------------------------------
def test_run_refuses_a_cpu_backend(capsys):
    code = run.main(["--workload", MANIFEST["workloads"][0]["name"],
                     "--seed", str(2**31 + 17), "--seconds", "1",
                     "--trace", "0"])
    out = capsys.readouterr()
    assert code != 0
    assert out.out == ""
    assert "no TPU" in out.err


def test_peaks_refuse_an_unknown_device():
    from bench.peaks import peaks

    assert peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")


def test_seed_past_32_bits_gives_the_same_tokens():
    from bench import weights

    a = weights.schedule_bits(2**33 + 5, [0.5, 0.5, 0.5], 64)
    b = weights.schedule_bits(2**33 + 5, [0.5, 0.5, 0.5], 64)
    assert (a == b).all() and 0 < a.mean() < 1
    k1 = weights.base_key(2**40 + 1)
    k2 = weights.base_key(2**40 + 1)
    import jax

    assert (jax.random.key_data(k1) == jax.random.key_data(k2)).all()
    assert not math.isnan(float(jax.random.normal(k1, ())))


# -- the window keeps the chip's queue full --------------------------------
def test_queue_depth_covers_queue_seconds():
    assert harness.queue_depth(0.156) == math.ceil(harness.QUEUE_S / 0.156)
    assert harness.queue_depth(harness.QUEUE_S * 3) == 1


def test_timed_window_waits_depth_steps_behind(monkeypatch):
    order, clock = [], iter(range(1, 100))
    monkeypatch.setattr(harness.time, "perf_counter", lambda: next(clock))

    class Loss:
        def __init__(self, k):
            self.k = k

        def __array__(self, dtype=None, copy=None):
            order.append(("wait", self.k))
            return np.zeros(1, dtype or np.float32)

        def is_ready(self):
            return self.k == 3

    class Built:
        @staticmethod
        def step(params, opt, batch, bits):
            order.append(("step", batch))
            return params, opt, Loss(batch), None

    out = harness.timed_window(Built, lambda k: (k, None), 0, 0, 3, 3.0,
                               0.0, depth=2)
    # step 3 is noted once it is ready; step 6 finds three in flight and
    # waits for the oldest; the window closes at the third completion and
    # waits for the steps still in flight
    assert order == [("step", 3), ("wait", 3), ("step", 4), ("step", 5),
                     ("step", 6), ("wait", 4), ("step", 7), ("wait", 5),
                     ("wait", 6), ("wait", 7)]
    assert out[2] == 8 and out[3] == [1, 2, 3, 4, 5] and out[4] == 0
