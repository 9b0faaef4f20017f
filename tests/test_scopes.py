"""The named scopes of the compiled train step, read as the benchmark
reads them (``bench.trace.scope_map`` over the compiled HLO text).

Inside ``fwd_bwd`` the model marks ``attention``, ``ffn``, ``ssd`` and
``head``; beside each ``gossip/matching{j}`` exchange the consensus
update runs under ``gossip/update``. The per-layer metrics
``attention_ms``, ``ffn_ms``, ``ssd_ms`` and ``head_ms`` and a later
consensus-update roofline read these scopes off the device trace, so
each must reach the compiled module, the backward pass and the
remat'd scan body included, and ``gossip/update`` must hold no
collective.

The one-node steps compile in-process on the one CPU device; the
4-node ring runs in a subprocess with four host devices (the repo's
convention, as in ``tests/test_telemetry.py``).
"""
import os
import re
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench.trace import in_scope, is_collective, scope_map  # noqa: E402

BLOCKS = ("attention", "ffn", "ssd", "head")
# "%name = <shape> <opcode>(": an instruction's name and opcode
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?\s([a-z][\w\-]*)\(")


def _collectives(hlo_text: str) -> list:
    """Names of the instructions whose opcode is a collective (on the
    CPU a ppermute keeps the jax name, ``ppermute.N``)."""
    out = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m and is_collective(m.group(2)):
            out.append(m.group(1))
    return out


def _fwd_bwd_blocks(smap) -> dict:
    """Block scope -> count of compiled ops under ``fwd_bwd`` and it."""
    out = dict.fromkeys(BLOCKS, 0)
    for scope in smap.values():
        if not in_scope(scope, "fwd_bwd"):
            continue
        for b in BLOCKS:
            out[b] += in_scope(scope, b)
    return out


def _local_step_hlo(arch: str) -> str:
    import jax
    import jax.numpy as jnp

    from repro.configs.registry import get_smoke_config
    from repro.dist import decen_train as dt
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import Model
    from repro.optim.optimizers import sgd

    cfg = get_smoke_config(arch)
    model = Model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    spec = dt.make_spec(mesh, cfg, multi_pod=False)
    opt = sgd(0.1, momentum=0.9)
    with jax.set_mesh(mesh):
        step = dt.make_train_step(model, opt, None, spec, gossip_mode="none")
        params = jax.eval_shape(lambda: dt.init_stacked_params(model, spec))
        ostate = jax.eval_shape(
            lambda: dt.init_stacked_opt_state(opt, model, spec))
        batch = {k: jax.ShapeDtypeStruct((1, 2, 32), jnp.int32)
                 for k in ("tokens", "labels")}
        bits = jax.ShapeDtypeStruct((0,), jnp.float32)
        return step.lower(params, ostate, batch, bits).compile().as_text()


@pytest.fixture
def kernels_interpreted(monkeypatch):
    """``auto`` resolves to the interpreted Pallas kernels, as it resolves
    to the compiled ones on the chip: the model takes the kernel paths
    the chip takes, on the CPU."""
    from repro.kernels import ops

    resolve = ops.resolve_mode
    monkeypatch.setattr(
        ops, "resolve_mode", lambda impl, off_tpu="xla": resolve(
            "interpret" if impl == "auto" else impl, off_tpu=off_tpu))


@pytest.mark.parametrize("arch,blocks", [
    ("internlm2_1_8b", {"attention", "ffn", "head"}),
    ("mamba2_370m", {"ssd", "head"}),
])
def test_model_scopes_reach_the_compiled_step(arch, blocks,
                                              kernels_interpreted):
    """Each block the model has is under ``fwd_bwd``, in the backward
    pass too; a block it lacks has no op. The SSD scan's kernel pair
    runs under ``ssd/kernel``, forward (and its remat recompute) and
    backward, where ``ssd_ms`` reads it."""
    smap = scope_map(_local_step_hlo(arch))
    counts = _fwd_bwd_blocks(smap)
    assert {b for b, n in counts.items() if n} == blocks, counts
    for b in blocks:
        assert any(in_scope(s, "fwd_bwd") and in_scope(s, b)
                   and "transpose" in s for s in smap.values()), b
    assert not any(in_scope(s, "gossip") for s in smap.values())
    kernel = [s for s in smap.values()
              if in_scope(s, "fwd_bwd") and in_scope(s, "ssd/kernel")]
    assert bool(kernel) == ("ssd" in blocks)
    if kernel:
        assert any("transpose" in s for s in kernel)
        assert any("transpose" not in s for s in kernel)


RING_HLO = """
    import sys
    import jax, jax.numpy as jnp

    from repro.configs.registry import get_smoke_config
    from repro.core import named_graph, plan_matcha
    from repro.dist import decen_train as dt
    from repro.launch.mesh import make_test_mesh
    from repro.models.transformer import Model
    from repro.optim.optimizers import sgd

    mode, out = sys.argv[1], sys.argv[2]
    cfg = get_smoke_config("internlm2_1_8b")
    model = Model(cfg)
    mesh = make_test_mesh(nodes=4, model=1)
    spec = dt.make_spec(mesh, cfg)
    plan = plan_matcha(named_graph("ring", 4, seed=3), 0.5, seed=0)
    opt = sgd(0.1, momentum=0.9)
    with jax.set_mesh(mesh):
        step = dt.make_train_step(model, opt, plan, spec, gossip_mode=mode)
        params = jax.eval_shape(lambda: dt.init_stacked_params(model, spec))
        ostate = jax.eval_shape(
            lambda: dt.init_stacked_opt_state(opt, model, spec))
        batch = {k: jax.ShapeDtypeStruct((4, 2, 32), jnp.int32)
                 for k in ("tokens", "labels")}
        bits = jax.ShapeDtypeStruct((plan.num_matchings,), jnp.float32)
        args = (params, ostate, batch, bits)
        if mode == "overlap":
            gstate = jax.eval_shape(lambda: dt.init_gossip_state(
                plan, spec, dt.param_bucket_plan(model)))
            args = (params, ostate, gstate, batch, bits)
        text = step.lower(*args).compile().as_text()
    with open(out, "w") as f:
        f.write(text)
    print(plan.num_matchings)
"""


@pytest.mark.parametrize("mode", ["masked", "overlap"])
def test_gossip_update_scope_beside_the_matchings(tmp_path, mode):
    """4-node ring: the update's ops are under ``gossip/update`` and no
    collective is; every collective of the step is a ppermute under its
    ``gossip/matching{j}``, one or more for each matching."""
    out = str(tmp_path / "step.hlo")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(RING_HLO), mode, out],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert res.returncode == 0, f"stderr:\n{res.stderr[-4000:]}"
    num_matchings = int(res.stdout.split()[-1])
    with open(out) as f:
        text = f.read()
    smap = scope_map(text)
    collectives = {n: smap.get(n, "") for n in _collectives(text)}
    assert collectives
    update = [n for n, s in smap.items() if in_scope(s, "gossip/update")]
    assert update
    assert not set(update) & set(collectives)
    assert all(in_scope(s, "gossip/matching*")
               for s in collectives.values()), collectives
    for j in range(num_matchings):
        assert any(in_scope(s, f"gossip/matching{j}")
                   for s in collectives.values()), j
    # the model's scopes come with the shared model code
    counts = _fwd_bwd_blocks(smap)
    assert counts["attention"] and counts["ffn"] and counts["head"], counts
