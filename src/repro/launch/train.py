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

``--trace DIR`` measures the run instead of only simulating it
(``repro.telemetry``): sequential modes execute through the *phased*
step builders (separately fenced executables per runtime phase), each
matching's exchange is probed as its own fenced ppermute, every step
prints a measured metrics line (step ms, comm ms, comm/compute overlap
ratio, modeled bytes), and on exit DIR receives ``events.jsonl``,
``metrics.jsonl``, and a Perfetto-loadable ``trace.json``. Fencing
costs dispatch overlap, so traced step times are an upper bound — see
``docs/observability.md``.

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
import dataclasses
import os
import time

import numpy as np


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
                    help="measure the run: device-synchronized per-phase "
                         "timers + per-matching comm probes, a per-step "
                         "metrics line, and on exit a JSONL event log "
                         "(events.jsonl) plus a Chrome trace (trace.json, "
                         "loads in chrome://tracing / Perfetto) in DIR. "
                         "Adds fencing overhead — leave off for "
                         "throughput runs (docs/observability.md)")
    return ap


def main(argv=None):
    """Run the driver on ``argv`` (default: ``sys.argv[1:]``).

    Returns the run's final state for in-process callers
    (``chip_smoke.py``): ``params`` (node-stacked), ``rows`` (the logged
    steps), ``plan``/``schedule`` (``None`` for a one-node run),
    ``bits`` (the last step's activation row) and ``spec``."""
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
        # --- telemetry (--trace DIR) -----------------------------------
        # A disabled StepTimer's spans are shared no-ops (identity
        # fence), so the untraced loop runs the byte-identical program.
        from repro.telemetry import StepTimer, TraceRecorder

        traced = bool(args.trace)
        recorder = None
        if traced:
            recorder = TraceRecorder(meta=dict(
                arch=args.arch, preset=args.preset, graph=args.graph,
                nodes=args.nodes, shard=args.shard, mode=args.mode,
                gossip_mode=gossip_mode, budget=args.budget,
                steps=args.steps, batch_per_node=args.batch_per_node,
                seq=args.seq, p_drop=args.p_drop,
                fault_seed=args.fault_seed,
            ))
        timer = StepTimer(recorder)
        # Phased executors (per-phase fenced timing) for the sequential
        # modes; overlap keeps the fused step — fencing its phases would
        # serialize the very overlap being measured — and is timed
        # whole-step with per-matching comm probes instead.
        phased = traced and gossip_mode != "overlap"
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
                    if phased:
                        step_cache[key] = fsdp.make_phased_fsdp_train_step(
                            model, opt, plan, spec, layout, timer=timer,
                            gossip_mode=gossip_mode, faulted=faulted,
                        )
                    else:
                        step_cache[key] = fsdp.make_fsdp_train_step(
                            model, opt, plan, spec, layout,
                            gossip_mode=gossip_mode, faulted=faulted,
                        )
                elif phased:
                    step_cache[key] = dt.make_phased_train_step(
                        model, opt, plan, spec, timer=timer,
                        gossip_mode=gossip_mode, active=tuple(active),
                        faulted=faulted,
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

        # comm probes: each matching's exchange measured as its own
        # fenced executable (once, up front; "comm" lane in the trace),
        # with the modeled per-matching bytes from analysis.bytes_model
        matching_ms = {}
        per_matching_bytes = 0
        if traced and plan is not None:
            from repro.analysis import bytes_model
            from repro.telemetry import probes as tprobes

            if use_fsdp:
                elems = int(layout.plan.total_elements)
                per_matching_bytes = int(bytes_model.bucket_plan_bytes(
                    layout.plan, 1)["per_matching_comm_bytes"])
            else:
                abs_local = jax.eval_shape(
                    lambda: model.init(jax.random.key(0))
                )
                elems = int(sum(
                    np.prod(l.shape) for l in jax.tree.leaves(abs_local)
                ))
                per_matching_bytes = bytes_model.tree_storage_bytes(abs_local)
            probe_rows = tprobes.measure_matchings(
                plan, spec, per_node_elements=elems, timer=timer, iters=3,
            )
            matching_ms = {r["matching"]: r["mean_ms"] for r in probe_rows}
            print("trace: per-matching comm probes "
                  + " ".join(f"m{r['matching']}={r['mean_ms']:.2f}ms"
                             for r in probe_rows))

        rows = []
        trace_rows = []
        sim_time = 0.0
        t0 = time.time()
        num_matchings = 0 if plan is None else plan.num_matchings
        bits = jnp.zeros((num_matchings,), jnp.float32)
        for k in range(start_step, args.steps):
            batch = next(it)
            active = () if schedule is None else schedule.active_indices(k)
            comm_units = 0.0 if schedule is None else schedule.comm_units(k)
            if faulted:
                # per-node effective rows: activation bit x link-survival
                # gate, symmetric across every matching edge (a dropped
                # exchange zeroes the delta at BOTH endpoints)
                bits = jnp.asarray(
                    fault_sched.node_bits(schedule.activations[k], k)
                )
            elif schedule is not None:
                bits = jnp.asarray(
                    schedule.activations[k].astype(np.float32)
                )
            stepf = get_step(active)
            t0s = time.perf_counter()
            with timer.phase("step", cat="step", step=k) as sp:
                if gossip_mode == "overlap":
                    params, opt_state, gstate, losses, metrics = stepf(
                        params, opt_state, gstate, batch, bits
                    )
                    # delayed gossip hides behind compute: the step costs
                    # the slower of the two, not their sum
                    sim_time += max(comm_units, 1.0)
                elif phased:
                    params, opt_state, losses, metrics = stepf(
                        params, opt_state, batch, bits, step=k
                    )
                    sim_time += comm_units + 1.0
                else:
                    params, opt_state, losses, metrics = stepf(
                        params, opt_state, batch, bits
                    )
                    # paper's delay model: one unit per activated matching
                    sim_time += comm_units + 1.0   # +1 compute
                sp.fence((params, losses))
            if fault_sched is not None:
                # stragglers stretch the simulated clock: the paper's
                # delay model is synchronous, so the step costs the
                # slowest node's extra units
                delay = fault_sched.max_delay(k)
                sim_time += delay
                if traced:
                    dropped = fault_sched.dropped_links(
                        schedule.activations[k], k
                    )
                    if dropped:
                        tprobes.fault_event(
                            recorder, step=k, kind="link_drop",
                            dropped_exchanges=dropped,
                        )
                    if delay:
                        tprobes.fault_event(
                            recorder, step=k, kind="straggler",
                            delay_units=delay,
                        )
            if traced:
                step_ms = (time.perf_counter() - t0s) * 1e3
                if phased:
                    comm_ms = stepf.last_phase_ms.get("gossip", 0.0)
                    phase_ms = stepf.last_phase_ms
                else:
                    comm_ms = sum(matching_ms.get(j, 0.0) for j in active)
                    phase_ms = None
                mrec = tprobes.step_metrics(
                    step=k, step_ms=step_ms, comm_ms=comm_ms,
                    gossip_mode=gossip_mode,
                    comm_bytes=per_matching_bytes * len(active),
                    phase_ms=phase_ms,
                )
                trace_rows.append(mrec)
                print(tprobes.format_metrics_line(mrec))
            if k % 10 == 0 or k == args.steps - 1:
                loss_mean = float(jnp.mean(losses))
                cons = float(consensus(params))
                rows.append(
                    dict(step=k, loss=loss_mean, consensus=cons,
                         sim_time=sim_time, comm_units=comm_units,
                         wall=time.time() - t0)
                )
                print(
                    f"step {k:4d} loss {loss_mean:.4f} consensus {cons:.3e} "
                    f"sim_time {sim_time:.0f}u active {len(active)}/{num_matchings}"
                )
            if args.ckpt_every and args.ckpt_dir and (k + 1) % args.ckpt_every == 0:
                # overlap: checkpoints land the in-flight exchange (the
                # live run keeps it pending — resuming with a fresh zero
                # GossipState then replays the uninterrupted trajectory)
                save_params = (
                    flush(params, gstate) if gossip_mode == "overlap"
                    else params
                )
                # crash-safe history layout: each checkpoint lands in
                # its own step_XXXXXXXX/ dir (ckpt.json written last as
                # the completeness marker) — a crash mid-save can never
                # damage an earlier restorable checkpoint. Transient
                # filesystem errors retry with bounded backoff.
                retry_with_backoff(lambda: ckpt_lib.save_run_step(
                    args.ckpt_dir, eval_params(save_params),
                    eval_opt_state(opt_state), step=k + 1,
                    extra={"shard": args.shard,
                           "stream_layers": bool(args.stream_layers),
                           "stream_scan": bool(args.stream_scan)},
                    keep_last=args.keep_last,
                ))
            if fault_spec.crash_at_step == k:
                if traced:
                    tprobes.fault_event(recorder, step=k, kind="crash")
                print(f"fault: simulated crash after completing step {k}")
                raise SimulatedCrash(k)

        if gossip_mode == "overlap":
            # land the exchange still in flight from the last step
            params = flush(params, gstate)
            cons = float(consensus(params))
            print(f"flushed in-flight gossip: consensus {cons:.3e}")

        if args.ckpt_dir:
            retry_with_backoff(lambda: ckpt_lib.save_run_step(
                args.ckpt_dir, eval_params(params), eval_opt_state(opt_state),
                step=args.steps, extra={"shard": args.shard,
                           "stream_layers": bool(args.stream_layers),
                           "stream_scan": bool(args.stream_scan)},
                keep_last=args.keep_last,
            ))
        if args.csv:
            os.makedirs(os.path.dirname(args.csv) or ".", exist_ok=True)
            import csv as csvmod

            with open(args.csv, "w", newline="") as f:
                w = csvmod.DictWriter(f, fieldnames=list(rows[0]))
                w.writeheader()
                w.writerows(rows)
            print("wrote", args.csv)

        if traced:
            import json

            jsonl_path, chrome_path = recorder.flush(args.trace)
            metrics_path = os.path.join(args.trace, "metrics.jsonl")
            with open(metrics_path, "w") as f:
                for r in trace_rows:
                    f.write(json.dumps(r) + "\n")
            print(f"wrote trace: {jsonl_path} + {chrome_path} "
                  f"({len(recorder.events())} events, "
                  f"{recorder.num_dropped} dropped) and {metrics_path}")
    return dict(params=params, rows=rows, plan=plan, schedule=schedule,
                bits=bits, spec=spec)


if __name__ == "__main__":
    main()
