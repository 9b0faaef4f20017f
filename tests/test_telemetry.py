"""repro.telemetry: schema round-trip, timer semantics, the
tracing-off no-change guarantee, and the train launcher's ``--trace``
(a profiler trace of the untraced run's own step).

The pure trace/timer tests run in-process (no jax device work). The
runtime tests follow the repo's subprocess convention (XLA host device
count must be set before jax initializes), like
tests/test_gossip_parity.py.
"""
import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(body)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert res.returncode == 0, f"stderr:\n{res.stderr[-4000:]}"
    return res.stdout


# ---------------------------------------------------------------------------
# trace schema
# ---------------------------------------------------------------------------
def _sample_events():
    from repro.telemetry import TraceEvent

    return [
        TraceEvent(name="step", cat="step", ts_us=100, dur_us=5000, step=0),
        TraceEvent(name="fwd_bwd", cat="phase", ts_us=150, dur_us=3000,
                   step=0, depth=1),
        TraceEvent(name="gossip/matching2", cat="comm", ts_us=9000,
                   dur_us=40, tid=1, args={"bytes": 1024, "mode": "probe"}),
    ]


def test_jsonl_round_trip(tmp_path):
    from repro.telemetry import read_jsonl, write_jsonl
    from repro.telemetry.trace import SCHEMA

    events = _sample_events()
    path = str(tmp_path / "events.jsonl")
    write_jsonl(events, path, meta={"arch": "x"}, dropped=3)
    header, back = read_jsonl(path)
    assert header["schema"] == SCHEMA
    assert header["meta"] == {"arch": "x"} and header["dropped"] == 3
    assert back == events


def test_jsonl_rejects_foreign_schema(tmp_path):
    import pytest

    from repro.telemetry import read_jsonl

    path = str(tmp_path / "events.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"schema": "someone.else/9"}) + "\n")
    with pytest.raises(ValueError):
        read_jsonl(path)


def test_chrome_trace_round_trip():
    """JSONL events -> Chrome trace -> events is lossless: step and
    depth (which the Chrome format has no field for) tunnel through
    args and come back out."""
    from repro.telemetry import from_chrome_trace, to_chrome_trace

    events = _sample_events()
    chrome = to_chrome_trace(events, meta={"arch": "x"}, dropped=0)
    assert all(e["ph"] == "X" for e in chrome["traceEvents"])
    assert from_chrome_trace(chrome) == events


def test_chrome_trace_files(tmp_path):
    from repro.telemetry import write_chrome_trace
    from repro.telemetry.trace import read_chrome_trace

    events = _sample_events()
    path = str(tmp_path / "trace.json")
    write_chrome_trace(events, path)
    with open(path) as f:
        blob = json.load(f)
    assert "traceEvents" in blob          # the Perfetto/chrome contract
    assert read_chrome_trace(path) == events


def test_ring_buffer_drops_oldest():
    from repro.telemetry import TraceEvent, TraceRecorder

    rec = TraceRecorder(capacity=4)
    for i in range(10):
        rec.record(TraceEvent(name=f"e{i}", cat="x", ts_us=i, dur_us=1))
    assert rec.num_dropped == 6
    assert [e.name for e in rec.events()] == ["e6", "e7", "e8", "e9"]


# ---------------------------------------------------------------------------
# timer semantics
# ---------------------------------------------------------------------------
def test_timer_monotone_and_nested_consistent():
    """Spans record positive durations, outer spans contain inner ones
    in time, and depth reflects nesting at record time."""
    import time

    from repro.telemetry import StepTimer, TraceRecorder

    rec = TraceRecorder()
    timer = StepTimer(rec)
    with timer.phase("step", step=0):
        with timer.phase("fwd_bwd", step=0):
            time.sleep(0.002)
        with timer.phase("optimizer", step=0):
            time.sleep(0.001)
    inner1, inner2, outer = rec.events()    # spans record on exit
    assert [e.name for e in (inner1, inner2, outer)] == [
        "fwd_bwd", "optimizer", "step"]
    assert outer.depth == 0 and inner1.depth == 1 and inner2.depth == 1
    for e in rec.events():
        assert e.dur_us > 0
    # containment: outer starts no later and ends no earlier
    assert outer.ts_us <= inner1.ts_us
    assert outer.ts_us + outer.dur_us >= inner2.ts_us + inner2.dur_us
    # monotone: second inner span starts after the first ends
    assert inner2.ts_us >= inner1.ts_us + inner1.dur_us


def test_timer_measure_returns_result_and_duration():
    from repro.telemetry import StepTimer, TraceRecorder

    rec = TraceRecorder()
    timer = StepTimer(rec)
    out, ms = timer.measure("probe", lambda: 41 + 1)
    assert out == 42 and ms >= 0.0
    assert rec.events()[-1].name == "probe"


def test_disabled_timer_is_structurally_free():
    """The tracing-off guarantee: a disabled timer's spans are one
    shared no-op object with an identity fence, ``timed_step`` returns
    the original function object, and ``measure`` still fences but
    records nothing."""
    from repro.telemetry import StepTimer, timed_step

    off = StepTimer(None)
    assert not off.enabled
    s1 = off.phase("step")
    s2 = off.phase("fwd_bwd", step=3)
    assert s1 is s2                      # shared singleton, no allocation
    obj = object()
    with s1 as sp:
        assert sp.fence(obj) is obj      # identity, no device sync

    def f(a, b):
        return a + b

    assert timed_step(f, off) is f       # byte-identical program when off
    out, ms = off.measure("x", lambda: 7)
    assert out == 7 and ms >= 0.0


def test_enabled_timer_requires_recorder():
    import pytest

    from repro.telemetry import StepTimer

    with pytest.raises(ValueError):
        StepTimer(None, enabled=True)


# ---------------------------------------------------------------------------
# tracing-off: no jaxpr / collective changes
# ---------------------------------------------------------------------------
def test_named_scope_and_fused_step_unchanged():
    """The phase annotations in the fused steps are jax.named_scope —
    metadata only. A named_scope-wrapped body must trace to the same
    equations, and the fused masked train step must still trace exactly
    the planned ppermute inventory (checked with the analysis pass the
    CI gate uses)."""
    run_sub("""
        import jax, jax.numpy as jnp
        import numpy as np

        def plain(x):
            return jnp.sin(x) * 2.0 + 1.0

        def scoped(x):
            with jax.named_scope("fwd_bwd"):
                return jnp.sin(x) * 2.0 + 1.0

        x = jnp.ones((4, 4))
        assert str(jax.make_jaxpr(plain)(x)) == str(jax.make_jaxpr(scoped)(x))

        from repro.analysis.checks import check_ppermutes
        from repro.analysis.collectives import collect
        from repro.analysis.traversal import to_closed_jaxpr
        from repro.configs.registry import get_smoke_config
        from repro.core import paper_figure1_graph, plan_matcha
        from repro.dist import decen_train as dt
        from repro.launch.mesh import make_test_mesh
        from repro.models.transformer import Model
        from repro.optim.optimizers import sgd

        cfg = get_smoke_config("internlm2_1_8b")
        model = Model(cfg)
        mesh = make_test_mesh(nodes=8, model=1)
        spec = dt.make_spec(mesh, cfg)
        plan = plan_matcha(paper_figure1_graph(), 0.5, budget_steps=400)
        opt = sgd(0.1, momentum=0.9)
        params = jax.eval_shape(lambda: dt.init_stacked_params(model, spec))
        ostate = jax.eval_shape(lambda: dt.init_stacked_opt_state(opt, model, spec))
        batch = {k: jax.ShapeDtypeStruct((8, 2, 16), jnp.int32)
                 for k in ("tokens", "labels")}
        bits = jnp.zeros((plan.num_matchings,), jnp.float32)
        step = dt.make_train_step(model, opt, plan, spec, gossip_mode="masked")
        closed = to_closed_jaxpr(step, params, ostate, batch, bits)
        records = collect(closed)
        viols = check_ppermutes(
            [r for r in records], num_nodes=8, node_axes=spec.node_axes,
            planned_pairs=plan.ppermute_pairs(), expect_all_planned=True,
            where="telemetry/fused",
        )
        assert not viols, viols
        print("OK")
    """)


# ---------------------------------------------------------------------------
# launcher smoke: --trace produces a loadable trace
# ---------------------------------------------------------------------------
TRACE_RUN = """
    import sys
    import jax, jax.numpy as jnp

    from repro.launch import train

    trace_dir, hlo_out = sys.argv[1], sys.argv[2]
    argv = ["--preset", "tiny", "--nodes", "4", "--graph", "ring",
            "--steps", "4", "--batch-per-node", "2", "--seq", "32"]
    traced = train.main(argv + ["--trace", trace_dir])
    plain = train.main(argv)
    assert set(traced["steps"]) == set(plain["steps"]) == {"masked"}
    batch = {k: jax.ShapeDtypeStruct((4, 2, 32), jnp.int32)
             for k in ("tokens", "labels")}
    with jax.set_mesh(traced["spec"].mesh):
        lowered = [out["steps"]["masked"].lower(
            out["params"], out["opt_state"], batch, out["bits"])
            for out in (traced, plain)]
        assert lowered[0].as_text() == lowered[1].as_text()
        with open(hlo_out, "w") as f:
            f.write(lowered[0].compile().as_text())
    print("OK")
"""


def test_train_trace_smoke(tmp_path):
    """--trace writes a profiler trace of steps 2 and 3: the host plane
    holds each step and the loop's data, dispatch and wait spans, the
    ops the CPU ran carry the step's named scopes, and the traced run's
    step is the untraced run's (the same lowered program)."""
    import glob

    out_dir = str(tmp_path / "trace")
    hlo = str(tmp_path / "step.hlo")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(TRACE_RUN), out_dir, hlo],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO,
    )
    assert res.returncode == 0, f"stderr:\n{res.stderr[-4000:]}"
    assert "trace: steps 2..3" in res.stdout and "OK" in res.stdout

    from jax.profiler import ProfileData

    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from bench.trace import in_scope, scope_map

    files = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    assert glob.glob(os.path.join(out_dir, "**", "perfetto_trace.json.gz"),
                     recursive=True)
    data = ProfileData.from_file(files[0])
    # on the CPU the host plane holds both the spans and the ops, each
    # op named as its HLO instruction
    names, steps = set(), []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                names.add(ev.name)
                if ev.name == "train":
                    steps.append(dict(ev.stats)["step_num"])
    assert {"train/data", "data/sample", "data/put", "train/dispatch",
            "train/wait"} <= names
    assert sorted(steps) == [2, 3]
    with open(hlo) as f:
        smap = scope_map(f.read())
    scopes = [smap[n] for n in names if n in smap]
    for want in ("attention", "ffn", "head"):
        assert any(in_scope(s, "fwd_bwd") and in_scope(s, want)
                   for s in scopes), want
    assert any(in_scope(s, "gossip/update") for s in scopes)
