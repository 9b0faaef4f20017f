"""End-to-end decentralized training of a ~100M-parameter model.

Drives the same trainer as ``repro.launch.train`` with a ~100M-param
internlm2-family config on 8 decentralized nodes (paper Fig-1 topology),
MATCHA CB=0.5, a few hundred steps. On CPU this takes a while at the
full 100M size, so ``--scale tiny`` (default, ~3M params / 100 steps)
runs the identical pipeline at smoke scale; ``--scale full`` runs the
real ~100M × 300-step configuration used for the reported curves.

Usage:
  PYTHONPATH=src python examples/train_decentralized.py            # tiny
  PYTHONPATH=src python examples/train_decentralized.py --scale full
"""
import argparse
import os

ap = argparse.ArgumentParser()
ap.add_argument("--scale", default="tiny", choices=("tiny", "full"))
ap.add_argument("--steps", type=int, default=0)
ap.add_argument("--budget", type=float, default=0.5)
ap.add_argument("--mode", default="matcha",
                choices=("matcha", "vanilla", "periodic"))
ap.add_argument("--gossip-mode", default="masked",
                choices=("masked", "sequential", "overlap"),
                help="masked/sequential: in-step exchange; overlap: "
                     "one-step-delayed bucketed gossip hidden behind the "
                     "fwd/bwd")
ap.add_argument("--shard", type=int, default=1,
                help="FSDP shard factor: each node keeps 1/N of the params "
                     "and optimizer state (repro.dist.fsdp)")
args = ap.parse_args()
if args.gossip_mode == "sequential":
    args.gossip_mode = "masked"   # same execution; keeps the branches below binary

os.environ.setdefault(
    "XLA_FLAGS",
    f"--xla_force_host_platform_device_count={8 * args.shard}",
)

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import paper_figure1_graph, plan_matcha, plan_periodic, plan_vanilla
from repro.data.pipeline import DecentralizedBatches
from repro.dist import decen_train as dt
from repro.dist import fsdp
from repro.dist import sharding as shd
from repro.launch.mesh import make_mesh
from repro.models.transformer import Model
from repro.optim.optimizers import sgd
from repro.checkpoint import ckpt as ckpt_lib

if args.scale == "full":
    # ~100M decoder (GQA, SwiGLU) — the end-to-end deliverable config
    cfg = ModelConfig(
        name="decen-100m", family="dense", num_layers=12, d_model=768,
        num_heads=12, num_kv_heads=4, d_ff=2048, vocab_size=32768,
        ffn_activation="silu", gated_ffn=True, pos_embed="rope",
        tie_embeddings=True, source="example",
    )
    steps = args.steps or 300
    batch_per_node, seq = 8, 256
else:
    cfg = ModelConfig(
        name="decen-3m", family="dense", num_layers=4, d_model=256,
        num_heads=4, num_kv_heads=2, d_ff=512, vocab_size=2048,
        ffn_activation="silu", gated_ffn=True, pos_embed="rope",
        tie_embeddings=True, source="example",
    )
    steps = args.steps or 100
    batch_per_node, seq = 4, 128

model = Model(cfg)
print(f"model: {cfg.name}  params ~{model.num_params()/1e6:.1f}M  "
      f"steps={steps}")

g = paper_figure1_graph()
if args.mode == "vanilla":
    plan = plan_vanilla(g)
elif args.mode == "periodic":
    plan, _ = plan_periodic(g, args.budget)
else:
    plan = plan_matcha(g, args.budget)
sched = plan.schedule(steps, seed=0)
print(f"{args.mode}: M={plan.num_matchings} alpha={plan.alpha:.3f} "
      f"rho={plan.rho:.4f} E[comm]={plan.expected_comm_units:.2f}u/iter")

if args.shard > 1:
    if batch_per_node % args.shard:
        raise SystemExit(f"batch_per_node {batch_per_node} must divide by "
                         f"--shard {args.shard}")
    mesh = make_mesh((8, args.shard, 1), ("data", "shard", "model"))
else:
    mesh = make_mesh((8, 1), ("data", "model"))
spec = dt.make_spec(mesh, cfg, multi_pod=False)
opt = sgd(0.15 if args.scale == "tiny" else 0.05, momentum=0.9)
layout = None
if args.shard > 1:
    layout = fsdp.make_layout(model, spec)
    params = fsdp.init_fsdp_params(model, layout, seed=0)
    opt_state = fsdp.init_fsdp_opt_state(opt, layout)
    pspecs = fsdp.fsdp_param_pspecs(spec, layout)
    print(f"fsdp shard={args.shard}: "
          f"{layout.per_device_elements * 4 / 1e6:.2f} MB params/device "
          f"(replica: {layout.plan.total_elements * 4 / 1e6:.2f} MB)")
else:
    params = dt.init_stacked_params(model, spec, seed=0)
    opt_state = dt.init_stacked_opt_state(opt, model, spec)
    pspecs = dt.stacked_param_shardings(model, spec)
data = DecentralizedBatches(cfg, 8, batch_per_node, seq, seed=0)
it = iter(data)


def eval_params(p):
    """Full stacked replicas (checkpointing only — O(model)/node)."""
    return fsdp.gather_params(layout, p) if args.shard > 1 else p


def consensus(p):
    if args.shard > 1:
        return fsdp.consensus_distance_sharded(p)
    return dt.consensus_distance(p)


losses_hist = []
sim_time = 0.0
gstate = None
if args.gossip_mode == "overlap":
    if args.shard > 1:
        gstate = fsdp.init_fsdp_gossip_state(layout)
        bplan = layout.plan
    else:
        bplan = dt.param_bucket_plan(model)
        gstate = dt.init_gossip_state(plan, spec, bplan)
    print(f"overlap gossip: {bplan.num_buckets} bucket(s), "
          f"{bplan.total_elements/1e6:.2f}M fp32 elements in flight")
with jax.set_mesh(mesh):
    params = jax.device_put(params, shd.named_shardings(pspecs, mesh))
    if args.shard > 1:
        step = fsdp.make_fsdp_train_step(
            model, opt, plan, spec, layout,
            gossip_mode=args.gossip_mode, grad_clip=1.0,
        )
    else:
        step = dt.make_train_step(
            model, opt, plan, spec, gossip_mode=args.gossip_mode,
            grad_clip=1.0,
            bucket_plan=bplan if args.gossip_mode == "overlap" else None,
        )
    for k in range(steps):
        bits = jnp.asarray(sched.activations[k].astype(np.float32))
        if args.gossip_mode == "overlap":
            params, opt_state, gstate, losses, metrics = step(
                params, opt_state, gstate, next(it), bits
            )
            # delayed exchange hides behind compute: max, not sum
            sim_time += max(sched.comm_units(k), 1)
        else:
            params, opt_state, losses, metrics = step(
                params, opt_state, next(it), bits
            )
            sim_time += sched.comm_units(k) + 1
        if k % 20 == 0 or k == steps - 1:
            loss_mean = float(jnp.mean(losses))
            losses_hist.append(loss_mean)
            print(f"step {k:4d} loss {loss_mean:.4f} "
                  f"consensus {float(consensus(params)):.2e} "
                  f"sim_time {sim_time:.0f}u")

    if args.gossip_mode == "overlap":
        # land the exchange still in flight from the last step
        if args.shard > 1:
            params = fsdp.make_fsdp_gossip_flush(plan, spec, layout)(
                params, gstate)
        else:
            params = dt.make_gossip_flush(model, plan, spec, bplan)(params, gstate)
        print(f"flushed in-flight gossip: consensus "
              f"{float(consensus(params)):.2e}")

assert losses_hist[-1] < losses_hist[0], "loss must decrease"
ckpt_dir = os.path.join("checkpoints", f"{cfg.name}-{args.mode}")
if args.shard > 1:
    ckpt_lib.save_run(ckpt_dir, eval_params(params),
                      fsdp.gather_opt_state(layout, opt_state), step=steps,
                      extra={"shard": args.shard})
else:
    ckpt_lib.save_run(ckpt_dir, params, opt_state, step=steps)
print(f"final loss {losses_hist[-1]:.4f} (from {losses_hist[0]:.4f}); "
      f"checkpoint -> {ckpt_dir}")
