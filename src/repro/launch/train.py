"""End-to-end decentralized training driver.

Runs MATCHA / vanilla DecenSGD / P-DecenSGD on a chosen architecture
(reduced or full config) over a chosen topology, with the pre-generated
a-priori schedule, simulated wall-clock accounting (the paper's linear
delay model: 1 unit per activated matching + compute), checkpointing and
CSV metrics.

CPU-friendly: with --preset tiny this trains a small transformer with
m=4..8 nodes on the real decentralized runtime (shard_map gossip) and
reproduces the paper's qualitative curves; the same driver drives the
full configs on a TPU pod.

``--shard N`` (N > 1) runs the FSDP-style sharded-replica mode
(``repro.dist.fsdp``): the mesh gains a ``shard`` axis, each node keeps
1/N of every param bucket + optimizer slot, and gossip exchanges the
shards directly (1/N of the bytes per matching). Checkpoints are
gathered on save, so the same directory restores into any shard factor
(and into the replicated runtime).

``--stream-layers`` (default ON whenever ``--shard > 1``) buckets the
shards per *layer group* instead of per byte target and streams the
fwd/bwd: each transformer block's group is all-gathered just-in-time
and its full-size view dropped when the block finishes (re-gathered in
the bwd), so peak transient memory is O(largest group) instead of
O(model). ``--no-stream-layers`` restores the monolithic gather. The
on-disk checkpoint format is identical either way (gather-on-save), so
runs restore across layouts freely.

``--stream-scan`` (default ON) extends the streaming INSIDE ``lax.scan``
segments: a scanned/periodic stack gathers one layer row per scan
iteration with double-buffered prefetch instead of one stack-sized
group, so deep scanned configs keep O(layer) peak transient memory with
scan compile times — unrolling via ``scan_layers=False`` is no longer
the answer. ``--no-stream-scan`` restores the stack-at-once gather for
A/B comparison.

``--trace DIR`` records a profiler trace of the run as it is: the same
fused step as an untraced run, under ``jax.profiler`` for
``TRACE_STEPS`` steps after the first ``TRACE_FROM`` (which compile).
DIR receives the ``.xplane.pb`` (TensorBoard's profile plugin, or
``bench/trace.py``) and a ``perfetto_trace.json.gz`` (ui.perfetto.dev).
Device ops carry the step's named scopes (``fwd_bwd`` with
``attention``/``ffn``/``ssd``/``head`` inside, ``optimizer``,
``gossip/matching{j}``, ``gossip/update``); the loop's host work is in
spans on the same clock (``train/data`` with ``data/sample`` and
``data/put``, ``train/dispatch``, ``train/wait``, ``train/checkpoint``,
``train/fault/<kind>``), so each device idle gap has the host span over
it. See ``docs/observability.md``.

``--p-drop P`` turns on the fault-injection layer (``repro.faults``):
a seeded :class:`~repro.faults.FaultSchedule` is declared up front
(exact reproducibility), each activated matching's link survives with
probability ``1 - P`` per step, and a dropped exchange degrades to
self-weight renormalization at BOTH endpoints so the effective mixing
matrix stays symmetric and doubly stochastic (``docs/fault_model.md``).
The planner's Theorem 2 gate is re-verified under the faulted
activation probabilities — a warning by default, a hard error with
``--strict-faults``. ``--straggler-prob``/``--straggler-units`` add
per-node straggler delays to the simulated clock; ``--crash-at-step K``
raises :class:`~repro.faults.SimulatedCrash` after completing step K
(and any checkpoint due at it), and ``--resume auto`` restarts from the
newest complete, checksum-valid checkpoint under ``--ckpt-dir``.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch internlm2_1_8b \
      --preset tiny --graph paper8 --nodes 8 --budget 0.5 --steps 100
  PYTHONPATH=src python -m repro.launch.train --preset tiny --nodes 4 \
      --shard 2 --gossip-mode overlap --steps 50
  PYTHONPATH=src python -m repro.launch.train --preset tiny --steps 20 \
      --trace out/trace
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import os
import time

import numpy as np

# --trace: the profiler records TRACE_STEPS steps from the run's
# TRACE_FROM-th on; the steps before it compile
TRACE_FROM = 2
TRACE_STEPS = 8


def build_parser() -> argparse.ArgumentParser:
    """The driver's CLI. Separate from :func:`main` so tooling
    (``repro.analysis.docs_lint``) can verify documented flags against
    the real parser without importing jax or running a step."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2_1_8b")
    ap.add_argument("--preset", default="tiny", choices=("tiny", "small", "full"))
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the preset's config to this many layers "
                         "(0: the preset's depth; small is at most 8)")
    ap.add_argument("--graph", default="paper8")
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--budget", type=float, default=0.5)
    ap.add_argument("--mode", default="matcha",
                    choices=("matcha", "vanilla", "periodic", "local"))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-per-node", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--gossip-mode", "--gossip-impl", dest="gossip_mode",
                    default="masked",
                    choices=("masked", "sequential", "static", "overlap"))
    ap.add_argument("--shard", type=int, default=1,
                    help="FSDP shard factor: each node keeps 1/N of the "
                         "params + optimizer state (N=1: full replicas)")
    ap.add_argument("--stream-layers", dest="stream_layers",
                    action=argparse.BooleanOptionalAction, default=None,
                    help="stream the fwd/bwd over per-layer-group buckets "
                         "(all-gather one block at a time; peak transient "
                         "memory O(largest group) instead of O(model)). "
                         "Default: on when --shard > 1")
    ap.add_argument("--stream-scan", dest="stream_scan",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="stream INSIDE lax.scan segments: gather one "
                         "layer row per scan iteration with double-"
                         "buffered prefetch, so deep scanned stacks keep "
                         "O(layer) peak transient memory. "
                         "--no-stream-scan restores the stack-at-once "
                         "gather (one near-model-sized group per scanned "
                         "segment). Requires --stream-layers")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--keep-last", type=int, default=3,
                    help="checkpoint history entries to keep under "
                         "--ckpt-dir (step_XXXXXXXX/ subdirectories; "
                         "0 keeps everything)")
    ap.add_argument("--resume", default="",
                    help="checkpoint directory to resume from, or "
                         "'auto' to resolve the newest complete, "
                         "checksum-valid checkpoint under --ckpt-dir "
                         "(torn/corrupt entries are skipped)")
    # --- fault injection (repro.faults, docs/fault_model.md) ---------
    ap.add_argument("--p-drop", type=float, default=0.0,
                    help="per-step probability each activated "
                         "matching's link drops for a node pair; the "
                         "dropped exchange degrades to self-weight "
                         "renormalization at both endpoints")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed of the a-priori FaultSchedule (same "
                         "seed => identical injected faults)")
    ap.add_argument("--straggler-prob", type=float, default=0.0,
                    help="per-step probability a node straggles, "
                         "adding --straggler-units to the simulated "
                         "step time")
    ap.add_argument("--straggler-units", type=float, default=1.0,
                    help="simulated delay units a straggling node "
                         "adds (the paper's clock: 1 unit per "
                         "activated matching)")
    ap.add_argument("--crash-at-step", type=int, default=-1,
                    help="raise SimulatedCrash after completing this "
                         "step (and any checkpoint due at it); -1 "
                         "disables")
    ap.add_argument("--strict-faults", action="store_true",
                    help="fail (instead of warn) when the injected "
                         "drop rate breaks Theorem 2: faulted rho >= 1 "
                         "or disconnected effective support")
    ap.add_argument("--csv", default="")
    ap.add_argument("--non-iid", action="store_true")
    ap.add_argument("--trace", default="", metavar="DIR",
                    help=f"write a profiler trace of {TRACE_STEPS} "
                         f"steps, after the first {TRACE_FROM}, to DIR: "
                         "the untraced run's step with "
                         "its named scopes on the device and the loop's "
                         "train/* and data/* spans on the host "
                         "(.xplane.pb for TensorBoard, "
                         "perfetto_trace.json.gz for ui.perfetto.dev; "
                         "docs/observability.md)")
    return ap


def main(argv=None):
    """Run the driver on ``argv`` (default: ``sys.argv[1:]``).

    Returns the run's final state for in-process callers
    (``chip_smoke.py``): ``params`` and ``opt_state`` (node-stacked),
    ``rows`` (the logged steps), ``plan``/``schedule`` (``None`` for a
    one-node run), ``bits`` (the last step's activation row), ``spec``
    and ``steps`` (the step executables the run built)."""
    args = build_parser().parse_args(argv)

    if args.nodes < 1:
        raise SystemExit(f"--nodes must be >= 1, got {args.nodes}")
    if args.layers < 0:
        raise SystemExit(f"--layers must be >= 0, got {args.layers}")
    if args.nodes == 1 and args.mode != "local":
        raise SystemExit(f"--mode {args.mode} gossips between nodes; one "
                         "node has no topology, so --nodes 1 needs "
                         "--mode local")
    if args.nodes == 1 and args.shard > 1:
        raise SystemExit("--nodes 1 runs the replicated runtime; "
                         "--shard must be 1")
    if args.shard < 1:
        raise SystemExit(f"--shard must be >= 1, got {args.shard}")
    if args.trace and args.steps <= TRACE_FROM:
        raise SystemExit(f"--trace records the steps after the first "
                         f"{TRACE_FROM}; --steps must be more")
    # "sequential" and "masked" are the same execution (every matching
    # exchanged in-step, deltas scaled by the schedule bits); both step
    # builders accept either spelling
    use_fsdp = args.shard > 1
    if args.stream_layers is None:
        args.stream_layers = use_fsdp
    if args.stream_layers and not use_fsdp:
        raise SystemExit("--stream-layers streams the sharded-replica "
                         "runtime; it requires --shard > 1")
    if use_fsdp and args.gossip_mode == "static":
        raise SystemExit("--shard > 1 supports --gossip-mode "
                         "sequential/masked or overlap, not static")
    if use_fsdp and args.batch_per_node % args.shard:
        raise SystemExit(
            f"--batch-per-node {args.batch_per_node} must divide by "
            f"--shard {args.shard} (the node's batch splits over the "
            "shard axis)")

    from repro.launch import runtime

    # device count must be set before jax import
    runtime.force_host_devices(args.nodes * args.shard * args.model_par)
    import jax
    import jax.numpy as jnp

    runtime.init_compile_cache()
    print(runtime.device_line())

    from repro.checkpoint import ckpt as ckpt_lib
    from repro.configs.registry import get_config, get_smoke_config
    from repro.core import (
        named_graph, plan_matcha, plan_periodic, plan_vanilla,
        vanilla_schedule, periodic_schedule,
    )
    from repro.data.pipeline import DecentralizedBatches
    from repro.dist import decen_train as dt
    from repro.faults import (
        FaultSpec, SimulatedCrash, make_fault_schedule,
        retry_with_backoff, verify_degraded_plan,
    )
    from repro.dist import fsdp
    from repro.dist import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import Model
    from repro.optim.optimizers import sgd

    cfg = (
        get_smoke_config(args.arch) if args.preset == "tiny"
        else get_config(args.arch)
    )
    if args.preset == "small":
        cfg = dataclasses.replace(
            get_config(args.arch),
            num_layers=min(get_config(args.arch).num_layers, 8),
        )
    if args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)

    def topology():
        graph = named_graph(args.graph, args.nodes, seed=3)
        if graph.m != args.nodes:
            raise SystemExit(f"graph has {graph.m} nodes, --nodes {args.nodes}")
        return graph

    fault_spec = FaultSpec(
        p_drop=args.p_drop,
        straggler_prob=args.straggler_prob,
        straggler_units=args.straggler_units,
        crash_at_step=args.crash_at_step,
        seed=args.fault_seed,
    )
    plan = schedule = None
    if args.nodes == 1:
        # local SGD on one node: no topology, no plan, no schedule
        if not fault_spec.empty:
            raise SystemExit("fault injection draws from a topology's "
                             "matchings; --nodes 1 has none")
    elif args.mode == "vanilla":
        plan = plan_vanilla(topology())
        schedule = vanilla_schedule(plan.matchings, args.steps)
    elif args.mode == "periodic":
        plan, _ = plan_periodic(topology(), args.budget)
        schedule = periodic_schedule(plan.matchings, args.budget, args.steps)
    else:
        plan = plan_matcha(topology(), args.budget, seed=args.seed)
        schedule = plan.schedule(args.steps, seed=args.seed)

    # --- fault injection (repro.faults) --------------------------------
    fault_sched = None
    faulted = fault_spec.has_link_faults
    if not fault_spec.empty:
        fault_sched = make_fault_schedule(plan, args.steps, fault_spec)
    if faulted and args.mode in ("matcha", "vanilla"):
        # Theorem 2 under faults: link drops rescale the activation
        # Bernoullis to p_eff = p * (1 - p_drop) exactly (same-matching
        # cross terms vanish — docs/fault_model.md), so the contraction
        # gate re-runs on the degraded probabilities.
        rho_f, problems = verify_degraded_plan(plan, fault_spec)
        if problems and args.strict_faults:
            raise SystemExit(
                "faults: --strict-faults: " + "; ".join(problems)
            )
        if problems:
            for msg in problems:
                print(f"faults: WARNING {msg}")
        else:
            print(f"faults: p_drop={args.p_drop:g} keeps the plan "
                  f"contractive (faulted rho {rho_f:.4f} < 1)")
    elif faulted:
        print(f"faults: mode {args.mode} has no independent-Bernoulli "
              "spectral gate; injecting drops without a rho-under-"
              "faults guarantee")

    if use_fsdp:
        mesh = make_mesh(
            (args.nodes, args.shard, args.model_par),
            ("data", "shard", "model"),
        )
    else:
        mesh = make_mesh((args.nodes, args.model_par), ("data", "model"))
    model = Model(cfg)
    opt = sgd(args.lr, momentum=args.momentum)
    spec = dt.make_spec(mesh, cfg, multi_pod=False)

    layout = None
    if use_fsdp:
        layout = (
            fsdp.make_stream_layout(model, spec, scan_aware=args.stream_scan)
            if args.stream_layers
            else fsdp.make_layout(model, spec)
        )
        params = fsdp.init_fsdp_params(model, layout, seed=args.seed)
        opt_state = fsdp.init_fsdp_opt_state(opt, layout)
        print(f"fsdp: shard={args.shard}, "
              f"{layout.per_device_elements * 4 / 1e6:.2f} MB params/device "
              f"(of {layout.plan.total_elements * 4 / 1e6:.2f} MB/replica)")
        if args.stream_layers:
            # the TRUE per-iteration peak: a scan-aware group streams
            # one layer row per scan iteration, so its contribution is
            # per_layer_elements (not repeats * per_layer_elements)
            peak = layout.plan.max_group_elements
            total = layout.plan.total_elements
            scanned = [
                (n, r) for n, r in
                zip(layout.plan.names, layout.plan.repeats) if r > 1
            ]
            print(f"fsdp: streaming {layout.plan.num_buckets} layer groups "
                  f"({', '.join(layout.group_names)}); per-iteration peak "
                  f"gathered view {peak * 4 / 1e6:.2f} MB vs "
                  f"{total * 4 / 1e6:.2f} MB monolithic")
            if scanned:
                print("fsdp: scan-streaming "
                      + ", ".join(f"{n} ({r} iterations/row gathers)"
                                  for n, r in scanned)
                      + " — double-buffered prefetch, <= 2 layer rows live")
            if not args.stream_scan and peak > 0.5 * total:
                # only reachable when scan streaming is explicitly
                # disabled: a stack-at-once scanned group keeps an
                # O(model)-sized gather
                print("fsdp: WARNING largest layer group is "
                      f"{100 * peak / total:.0f}% of the model — "
                      "--no-stream-scan keeps each scanned segment as "
                      "one stack-at-once gather; drop the flag to "
                      "stream per scan iteration")
    else:
        pspecs = dt.stacked_param_shardings(model, spec)
        ospecs = dt.stacked_opt_shardings(opt, model, spec, pspecs)
        # each node's replica is made on its own device: stacking on
        # one device first would need every node's state there
        params = jax.jit(
            lambda: dt.init_stacked_params(model, spec, seed=args.seed),
            out_shardings=shd.named_shardings(pspecs, mesh),
        )()
        opt_state = jax.jit(
            lambda: dt.init_stacked_opt_state(opt, model, spec),
            out_shardings=shd.named_shardings(ospecs, mesh),
        )()
    start_step = 0
    resume_dir = args.resume
    if resume_dir == "auto":
        # newest complete, checksum-valid checkpoint under --ckpt-dir
        # (torn entries from a crash mid-checkpoint are skipped)
        if not args.ckpt_dir:
            raise SystemExit("--resume auto requires --ckpt-dir")
        resume_dir = ckpt_lib.find_resumable(args.ckpt_dir) or ""
        if not resume_dir:
            print("resume auto: no restorable checkpoint under "
                  f"{args.ckpt_dir}; starting fresh")
    if resume_dir:
        # checkpoints are stored gathered (stacked), shard-agnostic;
        # transient read failures retry with bounded backoff
        r_params, r_opt, start_step = retry_with_backoff(
            lambda: ckpt_lib.restore_run(resume_dir)
        )
        if use_fsdp:
            params = fsdp.scatter_params(layout, r_params)
            opt_state = fsdp.scatter_opt_state(layout, opt, r_opt)
        else:
            params, opt_state = r_params, r_opt
        print(f"resumed from {resume_dir} at step {start_step}")

    if use_fsdp:
        pspecs = fsdp.fsdp_param_pspecs(spec, layout)
        ospecs = fsdp.fsdp_opt_pspecs(opt, spec, layout)
    with jax.set_mesh(mesh):
        # the steps donate params and opt_state: from here on only the
        # newest step output is read
        if use_fsdp or resume_dir:
            # fresh replicated state was made in place above
            params = jax.device_put(
                params, shd.named_shardings(pspecs, mesh))
            opt_state = jax.device_put(
                opt_state, shd.named_shardings(ospecs, mesh))
        gossip_mode = (
            "none" if args.mode == "local" else args.gossip_mode
        )
        gstate = flush = None
        if gossip_mode == "overlap":
            if use_fsdp:
                gstate = fsdp.init_fsdp_gossip_state(layout)
                flush = fsdp.make_fsdp_gossip_flush(plan, spec, layout)
            else:
                bplan = dt.param_bucket_plan(model)
                gstate = dt.init_gossip_state(plan, spec, bplan)
                flush = dt.make_gossip_flush(model, plan, spec, bplan)
        step_cache = {}

        def get_step(active):
            """static mode: one executable per distinct activated subset."""
            if gossip_mode != "static":
                key = gossip_mode
                active = ()
            else:
                key = tuple(active)
            if key not in step_cache:
                if use_fsdp:
                    step_cache[key] = fsdp.make_fsdp_train_step(
                        model, opt, plan, spec, layout,
                        gossip_mode=gossip_mode, faulted=faulted,
                    )
                else:
                    step_cache[key] = dt.make_train_step(
                        model, opt, plan, spec,
                        gossip_mode=gossip_mode, active=tuple(active),
                        bucket_plan=bplan if gossip_mode == "overlap" else None,
                        faulted=faulted,
                    )
            return step_cache[key]

        def eval_params(p):
            """Full stacked replicas (checkpointing only — gathering is
            O(model) per node, so the logging path must not use it)."""
            return fsdp.gather_params(layout, p) if use_fsdp else p

        def eval_opt_state(s):
            return fsdp.gather_opt_state(layout, s) if use_fsdp else s

        def consensus(p):
            if use_fsdp:
                return fsdp.consensus_distance_sharded(p)
            return dt.consensus_distance(p)

        ckpt_extra = {"shard": args.shard,
                      "stream_layers": bool(args.stream_layers),
                      "stream_scan": bool(args.stream_scan)}
        data = DecentralizedBatches(
            cfg, args.nodes, args.batch_per_node, args.seq,
            iid=not args.non_iid, seed=args.seed,
        )
        it = iter(data)
        # resume: replay the consumed prefix so step k sees the same
        # batch it would in an uninterrupted run (the pipeline is a
        # seeded stream, not step-indexed)
        for _ in range(start_step):
            next(it)

        rows = []
        sim_time = 0.0
        t0 = time.time()
        num_matchings = 0 if plan is None else plan.num_matchings
        bits = jnp.zeros((num_matchings,), jnp.float32)
        # --trace: one profiler window over the steps [trace_from,
        # trace_to); a span costs next to nothing when no trace runs
        span = jax.profiler.TraceAnnotation
        trace_from = start_step + TRACE_FROM if args.trace else -1
        trace_to = min(trace_from + TRACE_STEPS, args.steps)
        # no Python-call tracer: it would time every numpy call of the
        # host's sampler and slow the loop it records
        profile_options = jax.profiler.ProfileOptions()
        profile_options.python_tracer_level = 0
        with contextlib.ExitStack() as window:
            for k in range(start_step, args.steps):
                if k == trace_from:
                    window.enter_context(jax.profiler.trace(
                        args.trace, create_perfetto_trace=True,
                        profiler_options=profile_options))
                with jax.profiler.StepTraceAnnotation("train", step_num=k):
                    with span("train/data"):
                        batch = next(it)
                    active = (() if schedule is None
                              else schedule.active_indices(k))
                    comm_units = (0.0 if schedule is None
                                  else schedule.comm_units(k))
                    if faulted:
                        # per-node effective rows: activation bit x
                        # link-survival gate, symmetric across every
                        # matching edge (a dropped exchange zeroes the
                        # delta at BOTH endpoints)
                        bits = jnp.asarray(fault_sched.node_bits(
                            schedule.activations[k], k))
                    elif schedule is not None:
                        bits = jnp.asarray(
                            schedule.activations[k].astype(np.float32))
                    stepf = get_step(active)
                    with span("train/dispatch"):
                        if gossip_mode == "overlap":
                            params, opt_state, gstate, losses, metrics = (
                                stepf(params, opt_state, gstate, batch,
                                      bits))
                            # delayed gossip hides behind compute: the
                            # step costs the slower of the two, not
                            # their sum
                            sim_time += max(comm_units, 1.0)
                        else:
                            params, opt_state, losses, metrics = stepf(
                                params, opt_state, batch, bits)
                            # paper's delay model: one unit per
                            # activated matching, +1 compute
                            sim_time += comm_units + 1.0
                    if fault_sched is not None:
                        # stragglers stretch the simulated clock: the
                        # paper's delay model is synchronous, so the
                        # step costs the slowest node's extra units
                        delay = fault_sched.max_delay(k)
                        sim_time += delay
                        if fault_sched.dropped_links(
                                schedule.activations[k], k):
                            with span("train/fault/link_drop"):
                                pass
                        if delay:
                            with span("train/fault/straggler"):
                                pass
                    if k % 10 == 0 or k == args.steps - 1:
                        with span("train/wait"):
                            loss_mean = float(jnp.mean(losses))
                            cons = float(consensus(params))
                        rows.append(
                            dict(step=k, loss=loss_mean, consensus=cons,
                                 sim_time=sim_time, comm_units=comm_units,
                                 wall=time.time() - t0)
                        )
                        print(
                            f"step {k:4d} loss {loss_mean:.4f} consensus "
                            f"{cons:.3e} sim_time {sim_time:.0f}u active "
                            f"{len(active)}/{num_matchings}"
                        )
                    if (args.ckpt_every and args.ckpt_dir
                            and (k + 1) % args.ckpt_every == 0):
                        # overlap: checkpoints land the in-flight
                        # exchange (the live run keeps it pending —
                        # resuming with a fresh zero GossipState then
                        # replays the uninterrupted trajectory)
                        save_params = (
                            flush(params, gstate)
                            if gossip_mode == "overlap" else params
                        )
                        # crash-safe history layout: each checkpoint
                        # lands in its own step_XXXXXXXX/ dir (ckpt.json
                        # written last as the completeness marker) — a
                        # crash mid-save can never damage an earlier
                        # restorable checkpoint. Transient filesystem
                        # errors retry with bounded backoff.
                        with span("train/checkpoint"):
                            retry_with_backoff(
                                lambda: ckpt_lib.save_run_step(
                                    args.ckpt_dir, eval_params(save_params),
                                    eval_opt_state(opt_state), step=k + 1,
                                    extra=ckpt_extra,
                                    keep_last=args.keep_last,
                                ))
                    if fault_spec.crash_at_step == k:
                        with span("train/fault/crash"):
                            pass
                        print(f"fault: simulated crash after completing "
                              f"step {k}")
                        raise SimulatedCrash(k)
                if k == trace_to - 1 and args.trace:
                    # the window ends once its last step is done on the
                    # device
                    with span("train/wait"):
                        jax.block_until_ready((params, losses))
                    window.close()
                    print(f"trace: steps {trace_from}..{k} in {args.trace}")

        if gossip_mode == "overlap":
            # land the exchange still in flight from the last step
            params = flush(params, gstate)
            cons = float(consensus(params))
            print(f"flushed in-flight gossip: consensus {cons:.3e}")

        if args.ckpt_dir:
            retry_with_backoff(lambda: ckpt_lib.save_run_step(
                args.ckpt_dir, eval_params(params), eval_opt_state(opt_state),
                step=args.steps, extra=ckpt_extra, keep_last=args.keep_last,
            ))
        if args.csv:
            os.makedirs(os.path.dirname(args.csv) or ".", exist_ok=True)
            import csv as csvmod

            with open(args.csv, "w", newline="") as f:
                w = csvmod.DictWriter(f, fieldnames=list(rows[0]))
                w.writeheader()
                w.writerows(rows)
            print("wrote", args.csv)
    return dict(params=params, opt_state=opt_state, rows=rows, plan=plan,
                schedule=schedule, bits=bits, spec=spec,
                steps=dict(step_cache))


if __name__ == "__main__":
    main()
