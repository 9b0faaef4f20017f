"""The benchmark's one adapter to the program under test.

Every call into ``repro`` goes through this file. These entry points
are the benchmark's contract with the program:

  repro.launch.runtime.init_compile_cache, repro.launch.mesh.make_mesh,
  repro.configs.registry.get_config, repro.models.transformer.Model,
  repro.optim.optimizers.sgd, repro.core.named_graph / plan_matcha,
  repro.dist.decen_train.{make_spec, stacked_param_shardings,
  stacked_opt_shardings, init_stacked_opt_state, make_train_step}.

The step is built as ``repro.launch.train`` builds it for the same
flags; the benchmark makes the weights, the tokens and the schedule
bits itself and hands them to it.
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path
from typing import Any

SRC = Path(__file__).resolve().parents[1] / "src"


def _import_repro():
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def init_compile_cache() -> None:
    """The program's fixed cache directory, ``<checkout>/.jax_cache``
    (or ``JAX_COMPILATION_CACHE_DIR`` where that is set)."""
    _import_repro()
    from repro.launch import runtime

    runtime.init_compile_cache()


@dataclasses.dataclass
class Built:
    """The program's objects for one cell."""

    model: Any
    mesh: Any
    spec: Any
    plan: Any                   # None for a one-node run
    step: Any                   # jitted, donates params and opt_state
    param_shardings: Any        # NamedSharding tree of the stacked params
    init_opt_state: Any         # jitted () -> stacked optimizer state
    abstract_params: Any        # one node's parameter shapes
    nodes_axis: Any             # PartitionSpec entry of the node dim


def build(config: dict, traffic: dict, devices) -> Built:
    """Mesh, model, plan and jitted step for one cell, on ``devices``
    (one per node)."""
    _import_repro()
    import jax

    from repro.core import named_graph, plan_matcha
    from repro.dist import decen_train as dt
    from repro.dist import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import Model
    from repro.optim.optimizers import sgd

    cfg = model_config(config)
    nodes = int(traffic["nodes"])
    mesh = make_mesh((nodes, 1), ("data", "model"), devices=devices[:nodes])
    model = Model(cfg)
    opt = sgd(float(traffic["lr"]), momentum=float(traffic["momentum"]))
    spec = dt.make_spec(mesh, cfg, multi_pod=False)
    plan = None
    if traffic["mode"] == "matcha":
        graph = named_graph(traffic["graph"], nodes, seed=3)
        plan = plan_matcha(graph, float(traffic["budget"]), seed=0)
    elif traffic["mode"] != "local":
        raise ValueError(f"mode {traffic['mode']!r} has no cell yet")
    pspecs = dt.stacked_param_shardings(model, spec)
    ospecs = dt.stacked_opt_shardings(opt, model, spec, pspecs)
    param_sh = shd.named_shardings(pspecs, mesh)
    opt_sh = shd.named_shardings(ospecs, mesh)
    step = dt.make_train_step(
        model, opt, plan, spec, gossip_mode=traffic["gossip_mode"])
    init_opt = jax.jit(lambda: dt.init_stacked_opt_state(opt, model, spec),
                       out_shardings=opt_sh)
    abstract = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    return Built(
        model=model, mesh=mesh, spec=spec, plan=plan, step=step,
        param_shardings=param_sh, init_opt_state=init_opt, abstract_params=abstract,
        nodes_axis=spec.nodes_axis,
    )


def model_config(config: dict):
    """The program's ModelConfig of ``config["arch"]`` at the file's
    depth; every width as the program publishes it."""
    _import_repro()
    from repro.configs.registry import get_config

    return dataclasses.replace(get_config(config["arch"]),
                               num_layers=layers_of(config))


def layers_of(config: dict) -> int:
    return int(config.get("num_hidden_layers") or config["n_layer"])


def plan_edges(plan) -> list:
    """Each matching of the program's plan as a sorted list of edges."""
    out = []
    for perm in plan.permutations:
        out.append(sorted([i, int(j)] for i, j in enumerate(perm) if i < j))
    return out


def velocity(opt_state):
    """The SGD-momentum buffer: after one step from zero, the gradient
    as the optimizer got it."""
    return opt_state["velocity"]


def set_mesh(mesh):
    import jax

    return jax.set_mesh(mesh)
