"""Paper Figs. 4-6: error convergence vs epochs AND vs simulated wall-clock.

Trains the same reduced transformer decentralized over the paper's Fig-1
topology under: vanilla DecenSGD, MATCHA at several budgets, and
P-DecenSGD at the same budgets — on the REAL shard_map runtime (8-node
CPU mesh). Wall-clock uses the paper's linear delay model: each
iteration costs (#activated matchings + C) units, C = compute units.

Claims validated:
  * MATCHA CB=0.5 tracks vanilla's loss-vs-epoch curve (Fig 4 d-f);
  * at equal budget MATCHA's final loss <= P-DecenSGD's (Fig 6);
  * MATCHA reaches vanilla's final loss in less simulated time.

``convergence.csv`` also carries a measured ``wall_s`` column (fenced
per-step wall-clock, compilation step excluded) so time-to-loss can be
plotted on a real clock next to the simulated delay-model axis; the
measured values are reported but not gated — on the masked runtime all
matchings are traced regardless of budget, so CPU wall-clock barely
separates the budgets.
"""
from __future__ import annotations

import csv
import json
import os
import subprocess
import sys
import time

import numpy as np

COMPUTE_UNITS = 2.0     # compute cost per iteration, in link-time units


def run(out_dir: str = "benchmarks/results", steps: int = 120):
    """Entry point for benchmarks.run: the decentralized training needs an
    8-device CPU mesh, and XLA's host device count is locked at first jax
    init — so the training happens in a subprocess with XLA_FLAGS set and
    results come back as JSON."""
    t0 = time.time()
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.setdefault("PYTHONPATH", "src")
    res = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_convergence",
         "--worker", "--steps", str(steps), "--out", out_dir],
        capture_output=True, text=True, env=env, timeout=3600,
    )
    if res.returncode != 0:
        raise RuntimeError(f"convergence worker failed:\n{res.stderr[-3000:]}")
    payload = json.loads(res.stdout.splitlines()[-1])
    us = (time.time() - t0) * 1e6 / max(payload["n_rows"], 1)
    return payload["rows"], [tuple(c) for c in payload["checks"]], us


def _worker(out_dir: str, steps: int):
    import jax
    import jax.numpy as jnp

    from repro.configs.registry import get_smoke_config
    from repro.core import (
        paper_figure1_graph, plan_matcha, plan_periodic, plan_vanilla,
        periodic_schedule, vanilla_schedule,
    )
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

    runs = [("vanilla", None), ("matcha", 0.5), ("matcha", 0.25),
            ("periodic", 0.5), ("periodic", 0.25)]
    curves = {}
    rows = []
    for mode, cb in runs:
        if mode == "vanilla":
            plan = plan_vanilla(g)
            sched = vanilla_schedule(plan.matchings, steps)
            label = "vanilla"
        elif mode == "matcha":
            plan = plan_matcha(g, cb, budget_steps=800)
            sched = plan.schedule(steps, seed=1)
            label = f"matcha@{cb}"
        else:
            plan, _ = plan_periodic(g, cb)
            sched = periodic_schedule(plan.matchings, cb, steps)
            label = f"periodic@{cb}"

        opt = sgd(0.1, momentum=0.9)
        params = dt.init_stacked_params(model, spec, seed=0)
        opt_state = dt.init_stacked_opt_state(opt, model, spec)
        pspecs = dt.stacked_param_shardings(model, spec)
        data = DecentralizedBatches(cfg, 8, 4, 64, seed=0)
        it = iter(data)
        sim_time, wall_s, hist = 0.0, 0.0, []
        with jax.set_mesh(mesh):
            params = jax.device_put(params, shd.named_shardings(pspecs, mesh))
            step = dt.make_train_step(model, opt, plan, spec,
                                      gossip_mode="masked", grad_clip=1.0)
            for k in range(steps):
                bits = jnp.asarray(sched.activations[k].astype(np.float32))
                t0 = time.perf_counter()
                params, opt_state, losses, _ = step(
                    params, opt_state, next(it), bits
                )
                jax.block_until_ready(losses)
                if k > 0:      # step 0 pays compilation — keep it off the
                    wall_s += time.perf_counter() - t0      # measured axis
                sim_time += sched.comm_units(k) + COMPUTE_UNITS
                if k % 5 == 0 or k == steps - 1:
                    hist.append((k, float(jnp.mean(losses)), sim_time, wall_s))
        curves[label] = hist
        for k, loss_k, st, ws in hist:
            rows.append(dict(run=label, step=k, loss=round(loss_k, 5),
                             sim_time=round(st, 1), wall_s=round(ws, 3)))

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "convergence.csv"), "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=list(rows[0]))
        w.writeheader()
        w.writerows(rows)

    def final_loss(label):
        return curves[label][-1][1]

    def time_to_loss(label, target, axis=2):
        """First history value on the given time axis (2 = simulated
        units, 3 = measured wall-clock seconds) at which the run's loss
        reaches ``target``."""
        for point in curves[label]:
            if point[1] <= target:
                return point[axis]
        return float("inf")

    checks = []
    # (a) epoch-wise: matcha@0.5 within 5% of vanilla's final loss
    checks.append((
        f"matcha@0.5 final loss {final_loss('matcha@0.5'):.3f} ~ "
        f"vanilla {final_loss('vanilla'):.3f}",
        final_loss("matcha@0.5") <= final_loss("vanilla") * 1.05,
    ))
    # (b) matcha beats periodic at the same budget
    for cb in (0.5, 0.25):
        checks.append((
            f"matcha@{cb} <= periodic@{cb} final loss",
            final_loss(f"matcha@{cb}") <= final_loss(f"periodic@{cb}") * 1.02,
        ))
    # (c) wall-clock win: time for matcha@0.25 to reach vanilla's final loss
    tgt = final_loss("vanilla") * 1.02
    t_m = time_to_loss("matcha@0.25", tgt)
    t_v = time_to_loss("vanilla", tgt)
    checks.append((
        f"matcha@0.25 reaches vanilla-final loss in {t_m:.0f}u vs vanilla "
        f"{t_v:.0f}u",
        t_m <= t_v,
    ))
    # (d) measured wall-clock axis (informational: on the masked runtime
    # every matching is traced regardless of budget, so per-step
    # wall-clock barely varies with CB — the curve is emitted for the
    # time-to-loss plot, only its existence is asserted)
    t_mw = time_to_loss("matcha@0.25", tgt, axis=3)
    checks.append((
        f"measured: matcha@0.25 reaches vanilla-final loss in {t_mw:.1f}s "
        f"wall-clock (vanilla {time_to_loss('vanilla', tgt, axis=3):.1f}s)",
        bool(np.isfinite(t_mw)),
    ))
    return rows, checks


def build_parser():
    """CLI: ``--worker`` is the 8-device subprocess body spawned by
    :func:`run` (not for direct use)."""
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--out", default="benchmarks/results")
    return ap


if __name__ == "__main__":
    args = build_parser().parse_args()
    if args.worker:
        rows, checks = _worker(args.out, args.steps)
        print(json.dumps({"rows": rows, "checks": checks,
                          "n_rows": len(rows)}))
    else:
        _, checks, _ = run(steps=args.steps)
        for name, ok in checks:
            print(("PASS " if ok else "FAIL ") + name)
