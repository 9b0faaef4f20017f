"""The trace reduction on device traces recorded on a TPU v5e by
``fixtures/record_fixture.py``: two steps of a toy step with the
program's scope names (``fwd_bwd``, ``optimizer``, ``gossip``)."""
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401  (puts the repository on the path)

from bench import trace as tr

FIXTURES = Path(__file__).resolve().parent / "fixtures"


def _load(name):
    hlo = (FIXTURES / f"{name}.hlo.txt").read_text()
    return tr.load(str(FIXTURES / f"{name}.xplane.pb"),
                   {tr.module_name(hlo): tr.scope_map(hlo)})


@pytest.fixture(scope="module")
def one_chip():
    return _load("tpu_v5e_1chip")


def test_ops_names_and_modules(one_chip):
    assert sorted(one_chip.ops) == [0]
    ops = one_chip.ops[0]
    names = {o.name for o in ops}
    assert {"fusion.15", "fusion.4", "copy-done"} <= names
    assert all(o.module.startswith("jit_body") for o in ops)
    # HLO text in the event name, not the op's name
    assert not any("=" in o.name for o in ops)


def test_scopes_come_from_the_compiled_module(one_chip):
    ops = one_chip.ops[0]
    fwd = [o for o in ops if tr.in_scope(o.scope, "fwd_bwd")]
    assert {o.name for o in fwd} == {"fusion.15", "fusion.4"}
    assert all(not o.scope for o in ops if o.name.startswith("copy"))


def test_window_busy_and_idle(one_chip):
    t0, t1 = one_chip.window()
    ops = tr.clip(one_chip.ops[0], t0, t1)
    busy = tr.union_ns(ops)
    assert 0 < busy <= t1 - t0
    idle = sum(e - s for s, e in tr.gaps(ops, t0, t1))
    assert idle + busy == pytest.approx(t1 - t0)
    fwd = tr.self_time_ns(ops, lambda o: tr.in_scope(o.scope, "fwd_bwd"))
    assert 0 < fwd <= busy


def test_host_spans_are_read(one_chip):
    names = [n for n, _, _ in one_chip.spans]
    assert names.count("bench/dispatch") == 2
    assert names.count("bench/wait") == 2
    assert "bench/window" in names


HLO = """HloModule jit_body, entry_computation_layout={()}

%fused_computation.1 (p0: f32[4], p1: f32[4]) -> f32[1,4] {
  %a = f32[4]{0} add(f32[4]{0} %p0, f32[4]{0} %p1), metadata={op_name="jit(body)/optimizer/add"}
  %m = f32[4]{0} multiply(f32[4]{0} %a, f32[4]{0} %p1), metadata={op_name="jit(body)/optimizer/mul"}
  ROOT %b = f32[1,4]{1,0} broadcast(f32[4]{0} %m), metadata={op_name="jit(body)/broadcast_in_dim"}
}

%fused_computation.2 (p0: f32[4,4], p1: f32[4]) -> f32[4] {
  %d = f32[4]{0} dot(f32[4,4]{1,0} %p0, f32[4]{0} %p1), metadata={op_name="jit(body)/fwd_bwd/transpose(jvp())/dot_general"}
  %s = f32[4]{0} multiply(f32[4]{0} %d, f32[4]{0} %p1), metadata={op_name="jit(body)/optimizer/mul"}
  ROOT %t = f32[4]{0} subtract(f32[4]{0} %p1, f32[4]{0} %s), metadata={op_name="jit(body)/optimizer/sub"}
}

ENTRY %main (x: f32[4], w: f32[4,4]) -> f32[1,4] {
  %fusion.1 = f32[1,4]{1,0} fusion(f32[4]{0} %x, f32[4]{0} %x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(body)/broadcast_in_dim"}
  %fusion.2 = f32[4]{0} fusion(f32[4,4]{1,0} %w, f32[4]{0} %x), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(body)/fwd_bwd/transpose(jvp())/dot_general"}
  ROOT %copy.3 = f32[1,4]{1,0} copy(f32[1,4]{1,0} %fusion.1)
}
"""


def test_fused_update_takes_its_ops_scope():
    scopes = tr.scope_map(HLO)
    # own metadata names only the stacking: the fused ops' scope wins
    assert tr.in_scope(scopes["fusion.1"], "optimizer")
    # own metadata names a scope: it stays
    assert tr.in_scope(scopes["fusion.2"], "fwd_bwd")
    assert tr.module_name(HLO) == "jit_body"
