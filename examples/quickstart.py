"""Quickstart: the MATCHA pipeline end-to-end on the paper's 8-node graph.

Runs in seconds on CPU:
  1. decompose the Fig-1 topology into matchings (Misra-Gries),
  2. optimize activation probabilities at several communication budgets,
  3. solve for the optimal mixing weight alpha and the spectral norm rho,
  4. print the error-vs-communication trade-off table (paper Fig. 3a),
  5. run 60 steps of real decentralized training (8 nodes on a CPU mesh,
     shard_map gossip) comparing MATCHA CB=0.5 vs vanilla DecenSGD.

Usage: PYTHONPATH=src python examples/quickstart.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    matching_decomposition,
    paper_figure1_graph,
    plan_matcha,
    plan_periodic,
    plan_vanilla,
)


def spectral_table():
    g = paper_figure1_graph()
    ms = matching_decomposition(g)
    print(f"base graph: m={g.m} |E|={len(g.edges)} maxdeg={g.max_degree()}")
    print(f"matchings (Misra-Gries): M={len(ms)} sizes={[len(x.edges) for x in ms]}")
    vanilla = plan_vanilla(g)
    print(f"\nvanilla DecenSGD: rho={vanilla.rho:.4f} "
          f"comm={vanilla.vanilla_comm_units} units/iter")
    print(f"\n{'CB':>5} {'rho(MATCHA)':>12} {'rho(P-Decen)':>13} "
          f"{'E[comm]':>8} {'saving':>7}")
    for cb in (0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.75, 0.9, 1.0):
        m = plan_matcha(g, cb, budget_steps=800)
        p, _ = plan_periodic(g, cb)
        print(f"{cb:5.2f} {m.rho:12.4f} {p.rho:13.4f} "
              f"{m.expected_comm_units:8.2f} "
              f"{vanilla.vanilla_comm_units / max(m.expected_comm_units, 1e-9):6.1f}x")


def tiny_training_comparison():
    from repro.configs.registry import get_smoke_config
    from repro.data.pipeline import DecentralizedBatches
    from repro.dist import decen_train as dt
    from repro.dist import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import Model
    from repro.optim.optimizers import sgd

    g = paper_figure1_graph()
    cfg = get_smoke_config("internlm2_1_8b")
    model = Model(cfg)
    mesh = make_mesh((8, 1), ("data", "model"))
    spec = dt.make_spec(mesh, cfg, multi_pod=False)
    opt = sgd(0.2, momentum=0.9)

    results = {}
    for mode, cb in (("vanilla", 1.0), ("matcha", 0.5)):
        plan = plan_vanilla(g) if mode == "vanilla" else plan_matcha(g, cb)
        sched = plan.schedule(60, seed=1)
        params = dt.init_stacked_params(model, spec, seed=0)
        opt_state = dt.init_stacked_opt_state(opt, model, spec)
        pspecs = dt.stacked_param_shardings(model, spec)
        data = DecentralizedBatches(cfg, 8, 4, 64, seed=0)
        it = iter(data)
        sim_time = 0.0
        with jax.set_mesh(mesh):
            params = jax.device_put(params, shd.named_shardings(pspecs, mesh))
            step = dt.make_train_step(model, opt, plan, spec,
                                      gossip_mode="masked")
            for k in range(60):
                bits = jnp.asarray(sched.activations[k].astype(np.float32))
                params, opt_state, losses, _ = step(
                    params, opt_state, next(it), bits
                )
                sim_time += sched.comm_units(k) + 1
        results[mode] = (float(jnp.mean(losses)), sim_time)
        print(f"{mode:8s}: final loss {results[mode][0]:.4f} "
              f"simulated time {sim_time:.0f} units")
    v, m = results["vanilla"], results["matcha"]
    print(f"\nMATCHA reaches loss {m[0]:.3f} (vanilla {v[0]:.3f}) using "
          f"{m[1]/v[1]:.0%} of vanilla's simulated wall-clock.")


if __name__ == "__main__":
    print("=" * 64)
    print("MATCHA quickstart — paper Fig. 1 topology")
    print("=" * 64)
    spectral_table()
    print("\n" + "=" * 64)
    print("60-step decentralized training (8 nodes, real shard_map gossip)")
    print("=" * 64)
    tiny_training_comparison()
