"""Compile the chip path for a described TPU v5e 2x2 host (no chip).

The TPU compiler is installed with jax and compiles for a topology that
is described, not attached. It refuses what interpret mode accepts: a
Mosaic kernel left where the compiler would have to partition it, a
program that does not fit the chip's memory. These tests compile the
gossip kernel and the two train steps ``chip_smoke.py`` runs, at the
same sizes, and check the kernel is there and the memory fits.

The topology is described inside a module fixture (only the worker
that runs this file loads the TPU library), and ``ops._on_tpu`` is
steered inside each test so the hot path resolves to the compiled
kernel as it does on the chip.
"""
import dataclasses
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs.registry import get_config
from repro.core import complete_graph, plan_matcha, ring_graph
from repro.dist import decen_train as dt
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.models.transformer import Model
from repro.optim.optimizers import sgd

# internlm2-1.8B full-width leaves (embedding/head, ffn, norm) and a
# ragged one the wrapper pads
KERNEL_SHAPES = [(92544, 2048), (2048, 8192), (2048,), (1000, 37)]
HBM_BYTES = 16 * 2**30          # one v5e chip
HEADROOM = 0.9                  # chip_smoke's depths leave 10% free


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # compiles for a described chip cannot be read back here: keep
        # them out of a persistent cache, and the compiler's logs off /tmp
        mp.setenv("TPU_LOG_DIR", "disabled")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # no TPU compiler in this jax install
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    assert ops.resolve_mode("auto", off_tpu="interpret") == "pallas"


def _footprint(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _compile_step(topo, *, layers, nodes, gossip_mode, model_par=1,
                  arch="internlm2_1_8b", batch=(4, 128)):
    """The launcher's replicated train step at ``arch``'s widths (default
    internlm2-1.8B), ``batch`` sequences x tokens per node,
    ``model_par`` described chips per node."""
    cfg = dataclasses.replace(get_config(arch), num_layers=layers)
    model = Model(cfg)
    mesh = make_mesh((nodes, model_par), ("data", "model"),
                     devices=topo.devices[:nodes * model_par])
    spec = dt.make_spec(mesh, cfg)
    opt = sgd(0.05, momentum=0.9)
    graph = ring_graph(nodes) if nodes >= 3 else complete_graph(nodes)
    plan = None if gossip_mode == "none" else plan_matcha(graph, 0.5, seed=0)
    pspecs = dt.stacked_param_shardings(model, spec)
    ospecs = dt.stacked_opt_shardings(opt, model, spec, pspecs)

    def placed(abstract, specs):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
            abstract, specs)

    params = placed(jax.eval_shape(
        lambda: dt.init_stacked_params(model, spec)), pspecs)
    opt_state = placed(jax.eval_shape(
        lambda: dt.init_stacked_opt_state(opt, model, spec)), ospecs)
    batch = {k: jax.ShapeDtypeStruct(
        (nodes, *batch), jnp.int32, sharding=NamedSharding(mesh, P("data")))
        for k in ("tokens", "labels")}
    bits = jax.ShapeDtypeStruct(
        (0 if plan is None else plan.num_matchings,), jnp.float32,
        sharding=NamedSharding(mesh, P()))
    step = dt.make_train_step(model, opt, plan, spec, gossip_mode=gossip_mode)
    with jax.set_mesh(mesh):
        return step.lower(params, opt_state, batch, bits).compile()


@pytest.mark.parametrize("shape", KERNEL_SHAPES, ids=str)
def test_gossip_kernel_compiles_at_full_width(topo, on_tpu, shape):
    one_chip = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda a, b: ops.gossip_apply(a, b, 0.3)).lower(x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_one_chip_local_step_fits_hbm(topo, on_tpu):
    """chip_smoke's one-chip phase: 8 layers, one node, no gossip."""
    compiled = _compile_step(topo, layers=8, nodes=1, gossip_mode="none")
    m = compiled.memory_analysis()
    # params and optimizer state (all but the batch) are donated
    assert m.alias_size_in_bytes > 0.99 * m.argument_size_in_bytes
    assert _footprint(compiled) < HEADROOM * HBM_BYTES, _footprint(compiled)


def test_four_node_masked_step_compiles_with_kernel(topo, on_tpu):
    """chip_smoke's four-chip phase: 4 layers, 4 nodes on a ring, masked
    gossip through the compiled kernel inside the node-manual step."""
    compiled = _compile_step(topo, layers=4, nodes=4, gossip_mode="masked")
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text
    assert _footprint(compiled) < HEADROOM * HBM_BYTES, _footprint(compiled)


def test_tensor_parallel_masked_step_gathers_no_params(topo, on_tpu):
    """2 nodes x 2 tensor-parallel chips: the kernel runs on each leaf's
    local shards, so masked gossip adds no all-gather to the step
    without gossip (a gathered leaf would be held whole on every chip)."""
    masked = _compile_step(
        topo, layers=2, nodes=2, gossip_mode="masked", model_par=2).as_text()
    local = _compile_step(
        topo, layers=2, nodes=2, gossip_mode="none", model_par=2).as_text()
    assert "tpu_custom_call" in masked
    assert masked.count("all-gather") == local.count("all-gather")


def test_mamba2_step_takes_the_ssd_kernel(topo, on_tpu):
    """The benchmark's mamba2-370m cell: 48 layers, one node, 8 x 2048.
    The SSD scan lowers to the Pallas kernel pair under ``fwd_bwd`` and
    ``ssd/kernel`` (where ``ssd_ms`` reads it), and the step's footprint
    stays under the 11.80 GB of the jnp scan it replaces."""
    compiled = _compile_step(topo, layers=48, nodes=1, gossip_mode="none",
                             arch="mamba2_370m", batch=(8, 2048))
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    # forward, its remat recompute, backward
    assert len(calls) == 3, len(calls)
    for line in calls:
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        parts = [p for p in re.split(r"[/()]", op_name) if p]
        assert "fwd_bwd" in parts, op_name
        assert "ssd/kernel" in "/".join(parts), op_name
    assert _footprint(compiled) < 11.80e9, _footprint(compiled)
