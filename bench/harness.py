"""One run of one cell: set-up, a measured (or traced) window, the
comparison with the reference, and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:
``configs/<config>.json``, ``traffic/<traffic>.json``,
``metrics/<metric>.py``, ``flops/<family>.py``,
``reference/<family>.py`` and ``limits/<workload>.json``.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import glob
import importlib
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
SCHEDULE_ROWS = 8192
TRACE_STEPS = 8            # steps in a traced run's window
MIN_SAMPLE_S = 0.25        # least host-clock span of one step sample
QUEUE_S = 8.0              # device work kept in flight in the window


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell asks."""


# ---------------------------------------------------------------------------
# The cell, by name
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    @property
    def tokens_per_step(self) -> int:
        t = self.traffic
        return int(t["nodes"]) * int(t["batch_per_node"]) * int(t["seq"])


def load_cell(workload: str, bench_dir: Path = BENCH_DIR) -> Cell:
    root = bench_dir.parent
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return Cell(w, config, traffic,
                [m for m in manifest["end_to_end"] if mine(m)],
                [m for m in manifest["per_layer"] if mine(m)])


def metric_reader(name: str):
    return importlib.import_module(f"bench.metrics.{name}")


def flops_per_token(cell: Cell) -> float:
    mod = importlib.import_module(f"bench.flops.{cell.config['family']}")
    return float(mod.flops_per_token(cell.config, int(cell.traffic["seq"])))


# ---------------------------------------------------------------------------
# Statistics of the window
# ---------------------------------------------------------------------------
def step_samples(t_start: float, completions) -> tuple:
    """(ms per step over each group of consecutive steps, steps in a
    group). A step's time is the gap between consecutive completions; a
    group holds the fewest steps that span ``MIN_SAMPLE_S`` at the
    window's mean step, since a time read from the host's clock is off
    by about half a millisecond, and leave two samples or more. A last
    partial group is left out."""
    marks = [t_start] + list(completions)
    mean = (marks[-1] - marks[0]) / max(len(completions), 1)
    per = math.ceil(MIN_SAMPLE_S / mean) if mean > 0 else 1
    per = max(1, min(per, len(completions) // 2))
    out = [(marks[i] - marks[i - per]) * 1e3 / per
           for i in range(per, len(marks), per)]
    return out, per


def slowest_steps(t_start: float, completions, n: int = 3) -> list:
    """The ``n`` longest gaps between completions: [step, ms], step
    counted from the window's first."""
    marks = [t_start] + list(completions)
    gaps_ = [[i, (marks[i + 1] - marks[i]) * 1e3]
             for i in range(len(completions))]
    return sorted(gaps_, key=lambda g: -g[1])[:n]


def p90(values) -> float:
    """90th percentile (``statistics.quantiles``, inclusive method)."""
    if len(values) < 2:
        raise ValueError("p90 needs two samples or more")
    return statistics.quantiles(values, n=10, method="inclusive")[8]


# ---------------------------------------------------------------------------
# Per-layer metrics from the trace
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class TraceContext:
    """What a per-layer metric reader gets."""

    trace: object                   # bench.trace.Trace
    window: tuple                   # (t0, t1) ns
    devices: list                   # device ids in the trace
    steps: int
    tokens: int
    flops_per_token: float
    peaks: dict
    chips: int
    replica_elements: int
    bits_rows: list

    def device_ops(self):
        return [self.trace.ops.get(d, []) for d in self.devices]

    def scope_ms(self, pred) -> float:
        from bench.trace import clip, self_time_ns

        t0, t1 = self.window
        worst = max((self_time_ns(clip(ops, t0, t1), pred)
                     for ops in self.device_ops()), default=0.0)
        return worst / self.steps / 1e6


def breakdown(ctx: TraceContext) -> dict:
    from bench.trace import clip, gaps, segments, union_ns

    t0, t1 = ctx.window
    per_op = {}
    for ops in ctx.device_ops():
        for a, b, active in segments(clip(ops, t0, t1)):
            op = max(active, key=lambda o: (o.start, -o.end))
            tag = next((s for s in ("fwd_bwd", "optimizer", "gossip")
                        if s in op.scope), "other")
            key = f"{tag}:{op.name}"
            per_op[key] = per_op.get(key, 0.0) + (b - a) * 1e-9 / ctx.chips
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    if not ctx.devices:
        return {"device_ops": [], "idle_gaps": []}
    busiest = max(ctx.device_ops(),
                  key=lambda ops: union_ns(clip(ops, t0, t1)))
    named = []
    for s, e in gaps(clip(busiest, t0, t1), t0, t1):
        best, host = 0.0, "none"
        for n, hs, he in ctx.trace.spans:
            if n == "bench/window":
                continue
            overlap = min(e, he) - max(s, hs)
            if overlap > best:
                best, host = overlap, n
        named.append([host, (e - s) * 1e-9])
    named.sort(key=lambda x: -x[1])
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": named[:10]}


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------
def devices_for(cell: Cell, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: jax runs on {devs[0].platform}")
    if len(devs) < cell.chips:
        raise NoChip(f"{cell.name} needs {cell.chips} chips, jax sees "
                     f"{len(devs)}")
    return devs[:cell.chips]


def plan_matches(built, traffic) -> bool:
    from bench import program

    if built.plan is None:
        return not traffic.get("matchings")
    want = [sorted(sorted(e) for e in m) for m in traffic["matchings"]]
    return (program.plan_edges(built.plan) == want
            and abs(float(built.plan.alpha) - float(traffic["alpha"])) < 1e-6
            and np.allclose(built.plan.probabilities,
                            traffic["probabilities"]))


def make_state(cell: Cell, built, seed: int):
    """The seed's parameters, zero optimizer state, token maker and
    schedule bits, on the cell's devices."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bench import weights

    traffic, nodes = cell.traffic, int(cell.traffic["nodes"])
    bits_table = weights.schedule_bits(
        seed, traffic.get("probabilities", []), SCHEDULE_ROWS)
    params = weights.stacked_params(
        seed, built.abstract_params, nodes, built.param_shardings)
    opt = built.init_opt_state()
    gen = weights.token_maker(
        seed, int(cell.config["vocab_size"]), nodes,
        int(traffic["batch_per_node"]), int(traffic["seq"]),
        NamedSharding(built.mesh, P(built.nodes_axis)), bits_table,
        NamedSharding(built.mesh, P()))
    return params, opt, gen, bits_table


def first_steps(built, gen, params, opt, seed: int):
    """The first steps, through the window's own call and feed, with the
    program's readings for the comparison."""
    from bench import check, program

    losses, batches = [], []
    for k in range(check.STEPS):
        t0 = time.perf_counter()
        batch, bits = gen(k)
        batches.append((np.asarray(batch["tokens"]),
                        np.asarray(batch["labels"])))
        params, opt, loss, _ = built.step(params, opt, batch, bits)
        losses.append(np.asarray(loss, np.float64))
        step_s = time.perf_counter() - t0
        if k == 0:
            grad = check.leaf_norms(program.velocity(opt))
    change = check.change_norms(params, seed)
    prog = {"loss": np.stack(losses), "grad": grad, "change": change,
            "step_s": step_s}
    return params, opt, prog, batches


def _not_finite(loss) -> int:
    """Waits for ``loss``; 1 where any node's loss is not finite."""
    return int(not np.all(np.isfinite(np.asarray(loss))))


def timed_window(built, gen, params, opt, k: int, seconds: float,
                 t_start: float, depth: int):
    """Steps from ``k`` until ``seconds`` have passed since ``t_start``,
    with up to ``depth`` steps in flight: dispatch a step, note each
    earlier step whose loss is ready, and wait for the oldest once
    ``depth`` are in flight, as a trainer that logs with a lag does;
    then wait for the steps still in flight. Returns the state, the
    next step, each step's completion time and the count of non-finite
    losses."""
    completions, pending, failed = [], collections.deque(), 0
    while True:
        batch, bits = gen(k)
        # blocks while the runtime's own queue of launches is full
        params, opt, loss, _ = built.step(params, opt, batch, bits)
        k += 1
        pending.append(loss)
        while pending and (len(pending) > depth or pending[0].is_ready()):
            failed += _not_finite(pending.popleft())
            completions.append(time.perf_counter())
        if completions and completions[-1] - t_start >= seconds:
            break
    while pending:
        failed += _not_finite(pending.popleft())
        completions.append(time.perf_counter())
    return params, opt, k, completions, failed


def queue_depth(step_s: float) -> int:
    """Steps to keep in flight: enough for ``QUEUE_S`` of device work,
    so that a stall of the host's process shorter than that leaves the
    chip busy."""
    return max(1, math.ceil(QUEUE_S / max(step_s, 1e-3)))


def traced_window(built, gen, params, opt, k: int, steps: int, tdir: str):
    """``steps`` steps under the profiler, the host's work in spans of
    its own. Returns the state, the next step, the last step's inputs
    and the count of non-finite losses."""
    import jax

    span = jax.profiler.TraceAnnotation
    pending, failed = None, 0
    jax.profiler.start_trace(tdir)
    with span("bench/window"):
        for _ in range(steps):
            with span("bench/tokens"):
                batch, bits = gen(k)
            with span("bench/dispatch"):
                params, opt, loss, _ = built.step(params, opt, batch, bits)
            k += 1
            if pending is not None:
                with span("bench/wait"):
                    failed += _not_finite(pending)
            pending = loss
        with span("bench/wait"):
            failed += _not_finite(pending)
    jax.profiler.stop_trace()
    return params, opt, k, (batch, bits), failed


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_process: float, require_tpu: bool = True,
        bench_dir: Path = BENCH_DIR, log=sys.stderr) -> dict:
    """One run of ``workload``; returns the result line's object."""
    cell = load_cell(workload, bench_dir)
    import jax

    from bench import check, program
    from bench.peaks import peaks as peaks_of

    devs = devices_for(cell, require_tpu)
    kind = devs[0].device_kind
    print(f"device: platform {devs[0].platform} device_kind {kind} "
          f"count {len(devs)}", file=log)
    peaks = peaks_of(kind) if require_tpu else None
    program.init_compile_cache()
    # every program of the run in the cache, the small ones too, so that
    # a warm run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    traffic, config = cell.traffic, cell.config
    t_build = time.perf_counter()
    built = program.build(config, traffic, devs)
    plan_ok = plan_matches(built, traffic)
    t_built = time.perf_counter()
    out = {"metrics": {}, "device": {
        "platform": devs[0].platform, "kind": kind, "count": len(devs)}}
    with program.set_mesh(built.mesh):
        params, opt, gen, bits_table = make_state(cell, built, seed)
        jax.block_until_ready((params, opt))
        t_state = time.perf_counter()
        params, opt, prog, batches = first_steps(built, gen, params, opt,
                                                 seed)
        jax.block_until_ready((params, opt))
        t_start = time.perf_counter()
        setup_s = t_start - t_process
        print(f"setup: {t_build - t_process:.2f} s to the chip, "
              f"{t_built - t_build:.2f} s build, {t_state - t_built:.2f} s "
              f"weights, {t_start - t_state:.2f} s first steps", file=log)
        if not trace:
            depth = queue_depth(prog["step_s"])
            params, opt, k, completions, failed = timed_window(
                built, gen, params, opt, check.STEPS, seconds, t_start,
                depth)
            window = completions[-1] - t_start
            samples, per = step_samples(t_start, completions)
            values = {
                "tokens_per_s": len(completions) * cell.tokens_per_step
                / window,
                "step_ms_p90": p90(samples),
                "setup_s": setup_s,
            }
            for m in cell.end_to_end:
                out["metrics"][m["name"]] = {"value": values[m["name"]],
                                             "unit": m["unit"]}
            print(f"window: {len(completions)} steps in {window:.3f} s, "
                  f"{len(samples)} samples of {per} steps, {depth} in "
                  f"flight, slowest steps "
                  f"[step, ms] {slowest_steps(t_start, completions)}, "
                  f"setup {setup_s:.2f} s", file=log)
        else:
            tdir = tempfile.mkdtemp(prefix="bench-trace-")
            params, opt, k, last, failed = traced_window(
                built, gen, params, opt, check.STEPS, TRACE_STEPS, tdir)
            hlo = built.step.lower(params, opt, *last).compile().as_text()
            rows = [bits_table[(check.STEPS + i) % SCHEDULE_ROWS]
                    for i in range(TRACE_STEPS)]
            reduced = _reduce_trace(cell, tdir, hlo, TRACE_STEPS, peaks,
                                    rows, built, log)
            out["metrics"] = reduced["metrics"]
            out["device"].update(reduced["device"])
            out["breakdown"] = reduced["breakdown"]
            shutil.rmtree(tdir, ignore_errors=True)
        out["attempted"] = k - check.STEPS
        out["failed"] = failed
        stats = [d.memory_stats() or {} for d in devs]
        print(f"memory_stats: {stats[0]}", file=log)
        # the arrays' peak and the region the runtime reserves for the
        # executables' temporaries, which the arrays' peak leaves out
        out["device"]["memory_peak_bytes"] = max(
            int(s.get("peak_bytes_in_use", 0))
            + int(s.get("peak_bytes_reserved", 0)) for s in stats)
        del params, opt
        gc.collect()

    # the plain reference, once the program's state is freed
    t_ref = time.perf_counter()
    ref = check.reference_readings(
        config["family"], config, traffic, built.abstract_params, seed,
        batches, bits_table[:check.STEPS], devs)
    numbers = check.compare(prog, ref)
    numbers["plan_mismatch"] = 0.0 if plan_ok else 1.0
    limits = check.load_limits(bench_dir, cell.name)
    ok, rows = check.judge(numbers, limits)
    for name in check.NAMES:
        if name not in limits:
            print(f"not compared: {name} {numbers[name]!r}", file=log)
    print(f"reference: {time.perf_counter() - t_ref:.1f} s", file=log)
    print(f"losses: program {prog['loss'].tolist()} reference "
          f"{ref['loss'].tolist()}", file=log)
    out["correct"] = bool(ok and out["failed"] == 0)
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, v, lim in rows}
    return out


def _reduce_trace(cell, tdir, hlo, steps, peaks, bits_rows, built, log):
    import jax

    from bench import trace as tr

    files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {tdir}")
    trace = tr.load(max(files, key=os.path.getmtime),
                    {tr.module_name(hlo): tr.scope_map(hlo)})
    window = trace.window()
    chips = cell.chips
    devices = sorted(trace.ops)[:chips]
    replica = int(sum(math.prod(a.shape)
                      for a in jax.tree.leaves(built.abstract_params)))
    ctx = TraceContext(
        trace=trace, window=window, devices=devices, steps=steps,
        tokens=steps * cell.tokens_per_step,
        flops_per_token=flops_per_token(cell), peaks=peaks, chips=chips,
        replica_elements=replica, bits_rows=bits_rows)
    metrics = {}
    for m in cell.per_layer:
        value = metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    busy = [tr.union_ns(tr.clip(ops, *window)) for ops in ctx.device_ops()]
    scoped = sum(1 for ops in ctx.device_ops() for o in ops if o.scope)
    total = sum(len(ops) for ops in ctx.device_ops())
    print(f"trace: {total} device ops on {len(devices)} chips, {scoped} "
          f"with a scope; planes {sorted(trace.ops)}", file=log)
    return {
        "metrics": metrics,
        "device": {"busy_s": float(np.mean(busy)) * 1e-9 if busy else 0.0,
                   "window_s": (window[1] - window[0]) * 1e-9},
        "breakdown": breakdown(ctx),
    }
