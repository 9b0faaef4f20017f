"""CLI: statically verify every execution strategy's traced program.

    PYTHONPATH=src python -m repro.analysis.check \\
        --preset tiny --shard 2 --all-layouts --strict

Traces each train-step variant (replicated gossip modes x fsdp layouts
x fsdp gossip modes, plus the serve prefill/decode steps) to a closed
jaxpr — nothing executes, nothing is allocated — and checks:

* collective inventory + axis contract (``repro.analysis.collectives``
  against the dist modules' ``COLLECTIVE_CONTRACT`` declarations),
* matching validity of every traced ppermute against the plan,
* byte budgets against the analytic model (``bytes_model``) and the
  committed ``benchmarks/results/BENCH_comm_time.json``,
* the memory-ladder bound per layout (traced with gossip "none" — see
  ``checks.check_memory_ladder``),
* the dtype lint (no f64; dist-layer fp32 upcasts only at declared
  ``FP32_UPCAST_SITES``),
* below the jaxpr: every pallas_call reachable from the registry's
  kernel shapes against its ``KERNEL_CONTRACT`` (``--kernel-sweep
  arch`` lints the selected arch, ``registry`` sweeps all ten,
  ``none`` skips — see ``repro.analysis.pallas_lint``), plus the
  hardcoded-``interpret=`` source lint,
* above the jaxpr: Theorem 2's convergence condition for the plan —
  exact rho = ||E[W'W] - J||_2 < 1, expectation-graph connectivity,
  sampler agreement (``repro.analysis.schedule``), and optionally the
  committed spectral CSV (``--spectral-csv``),
* with ``--faults``: the degraded-mode lanes (``docs/fault_model.md``)
  — every gossiping strategy re-traced with the fault-injection
  ``faulted=True`` step builders (per-node degradation gate rows) and
  held to the SAME collective-inventory, matching, dtype, and byte
  contracts (a dropped exchange still issues its ppermute; only the
  delta is gated), plus the degraded spectral gate
  (``check_faulted_spectral`` at ``--p-drop``) and the numeric
  doubly-stochastic check on sampled faulted mixing matrices
  (``check_degraded_mixing``).

``--skip-steps`` elides the step tracing for kernel/schedule-only runs.
Emits a JSON report on stdout (progress on stderr). ``--strict`` exits
1 on any violation — the CI gate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPLICATED_MODES = ("masked", "static", "overlap", "none")
FSDP_MODES = ("sequential", "overlap", "none")
LAYOUTS = ("monolithic", "streamed", "scan_streamed")
ARTIFACT = os.path.join("benchmarks", "results", "BENCH_comm_time.json")


def build_parser() -> argparse.ArgumentParser:
    """The checker's CLI. Separate from :func:`_parse` so tooling
    (``repro.analysis.docs_lint``) can verify documented flags against
    the real parser without importing jax."""
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.check", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--arch", default="internlm2_1_8b")
    ap.add_argument("--preset", default="tiny", choices=("tiny", "small"))
    ap.add_argument("--graph", default="ring")
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--budget", type=float, default=0.5)
    ap.add_argument("--shard", type=int, default=1)
    ap.add_argument("--batch-per-node", type=int, default=4)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument(
        "--layouts", default=",".join(LAYOUTS),
        help="comma list from " + ",".join(LAYOUTS),
    )
    ap.add_argument(
        "--all-layouts", action="store_true",
        help="check every fsdp layout (same as the default --layouts)",
    )
    ap.add_argument(
        "--gossip-modes", default="all",
        help="'all' or a comma list (replicated: "
        + ",".join(REPLICATED_MODES) + "; fsdp: " + ",".join(FSDP_MODES)
        + "; masked/sequential alias each other)",
    )
    ap.add_argument(
        "--artifact", default=ARTIFACT,
        help="BENCH_comm_time.json to cross-check (skipped if missing)",
    )
    ap.add_argument(
        "--kernel-sweep", default="arch",
        choices=("arch", "registry", "none"),
        help="Pallas kernel lint scope: the selected --arch, every "
        "registry arch, or skip",
    )
    ap.add_argument(
        "--skip-steps", action="store_true",
        help="skip the step tracing (kernel/schedule checks only)",
    )
    ap.add_argument(
        "--faults", action="store_true",
        help="add the fault-injection lanes: faulted step traces "
        "(per-node degradation gates) checked against the same "
        "collective/byte contracts, plus the degraded spectral gate "
        "and doubly-stochastic mixing check (docs/fault_model.md)",
    )
    ap.add_argument(
        "--p-drop", type=float, default=0.3,
        help="link-drop probability the --faults lanes verify at",
    )
    ap.add_argument(
        "--spectral-csv", default="",
        help="re-derive this committed spectral_norm_vs_budget.csv "
        "from the planner (skipped when empty)",
    )
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 on any violation (the CI gate)")
    ap.add_argument("--out", default="",
                    help="also write the JSON report to this path")
    return ap


def _parse(argv):
    ap = build_parser()
    args = ap.parse_args(argv)
    if args.all_layouts:
        args.layouts = ",".join(LAYOUTS)
    layouts = tuple(s for s in args.layouts.split(",") if s)
    for s in layouts:
        if s not in LAYOUTS:
            ap.error(f"unknown layout {s!r}; choose from {LAYOUTS}")
    args.layouts = layouts
    if args.gossip_modes == "all":
        args.modes = None
    else:
        modes = set(s for s in args.gossip_modes.split(",") if s)
        if "masked" in modes or "sequential" in modes:
            modes |= {"masked", "sequential"}
        args.modes = modes
    if args.shard < 1:
        ap.error(f"--shard must be >= 1, got {args.shard}")
    if args.batch_per_node % args.shard:
        ap.error(
            f"--batch-per-node {args.batch_per_node} must divide by "
            f"--shard {args.shard}"
        )
    return args


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    args = _parse(argv)
    # device count must be set before jax import (launch/train.py pattern)
    ndev = args.nodes * max(args.shard, 1)
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={ndev}"
    )
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis import bytes_model, checks
    from repro.analysis.collectives import collect
    from repro.analysis.traversal import max_fp_intermediate, to_closed_jaxpr
    from repro.configs.registry import get_config, get_smoke_config
    from repro.core import named_graph, plan_matcha
    from repro.core.matching import validate_permutations
    from repro.dist import decen_train as dt
    from repro.dist import fsdp
    from repro.dist import serve as sv
    from repro.dist import sharding as shd
    from repro.launch.mesh import make_mesh, make_test_mesh
    from repro.models.transformer import Model
    from repro.optim.optimizers import sgd

    cfg = (
        get_smoke_config(args.arch) if args.preset == "tiny"
        else get_config(args.arch)
    )
    model = Model(cfg)
    opt = sgd(0.05, momentum=0.9)
    graph = named_graph(args.graph, args.nodes, seed=3)
    plan = plan_matcha(graph, args.budget, budget_steps=200, seed=0)

    def want(mode: str) -> bool:
        return args.modes is None or mode in args.modes

    report = {
        "arch": args.arch,
        "preset": args.preset,
        "graph": args.graph,
        "nodes": args.nodes,
        "shard": args.shard,
        "budget": args.budget,
        "num_matchings": plan.num_matchings,
        "steps": {},
        "plan": {"violations": []},
        "schedule": {"violations": []},
        "kernels": {"cases": {}, "interpret_lint": []},
        "artifact": {"path": args.artifact, "row": None, "violations": []},
    }
    all_violations = []

    def record_step(label, closed, records, viols, max_fp=None):
        report["steps"][label] = {
            "num_eqns_top": len(closed.jaxpr.eqns),
            "collectives": [r.to_json() for r in records],
            "max_fp_intermediate": max_fp,
            "violations": [v.to_json() for v in viols],
        }
        all_violations.extend(viols)
        _log(
            f"  {label}: {len(records)} collectives, "
            f"{len(viols)} violations"
        )

    # -- plan metadata -------------------------------------------------------
    try:
        validate_permutations(plan.permutations, graph.m)
    except ValueError as e:  # MatchaPlan.__post_init__ already raises;
        # re-reported here so a hand-built plan still yields a report
        v = checks.Violation("plan-invalid", str(e), "plan")
        report["plan"]["violations"].append(v.to_json())
        all_violations.append(v)
    planned_pairs = plan.ppermute_pairs()

    # -- schedule verifier: Theorem 2's convergence condition ----------------
    from repro.analysis import schedule as schedule_checks

    _log("schedule verifier: exact rho / connectivity / sampler")
    sviols = schedule_checks.check_plan_spectral(plan, where="schedule/plan")
    sviols += schedule_checks.check_empirical_rho(
        plan, where="schedule/empirical"
    )
    if args.spectral_csv:
        _log(f"  re-deriving {args.spectral_csv} (deterministic rebuild)")
        sviols += schedule_checks.check_spectral_csv(
            args.spectral_csv, where="schedule/csv"
        )
    if args.faults:
        _log(f"  degraded-mode gates at p_drop={args.p_drop:g}")
        sviols += schedule_checks.check_faulted_spectral(
            plan, args.p_drop, where="schedule/faulted"
        )
        sviols += schedule_checks.check_degraded_mixing(
            plan, p_drop=args.p_drop, where="schedule/degraded-mixing"
        )
    report["schedule"]["violations"] = [v.to_json() for v in sviols]
    all_violations.extend(sviols)
    _log(f"  schedule: {len(sviols)} violations")

    # -- kernel lint: below the jaxpr ----------------------------------------
    if args.kernel_sweep != "none":
        from repro.analysis import kernel_cases, pallas_lint

        sweep_arch = args.arch if args.kernel_sweep == "arch" else None
        kcases = kernel_cases.sweep_cases(sweep_arch)
        _log(f"kernel lint: {len(kcases)} cases ({args.kernel_sweep})")
        for case in kcases:
            kviols, stats = pallas_lint.lint_case(case)
            report["kernels"]["cases"][case.label] = {
                "stats": stats,
                "violations": [v.to_json() for v in kviols],
            }
            all_violations.extend(kviols)
        lint = pallas_lint.check_interpret_literals()
        report["kernels"]["interpret_lint"] = [v.to_json() for v in lint]
        all_violations.extend(lint)
        nkv = sum(
            len(c["violations"]) for c in report["kernels"]["cases"].values()
        ) + len(lint)
        _log(f"  kernels: {nkv} violations")

    def emit() -> int:
        report["num_violations"] = len(all_violations)
        report["ok"] = not all_violations
        out = json.dumps(report, indent=2)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                f.write(out + "\n")
        print(out)
        if all_violations:
            _log(f"FAIL: {len(all_violations)} violations")
            for v in all_violations[:20]:
                _log(f"  [{v.name}] {v.where}: {v.detail}")
            return 1 if args.strict else 0
        _log("OK: all checks passed")
        return 0

    if args.skip_steps:
        return emit()

    abs_local = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    bits = jnp.zeros((plan.num_matchings,), jnp.float32)
    # faulted lanes trace with the per-node effective-row shape the
    # fault schedule hands the runtime (activation x link gate)
    bits_f = jnp.zeros((args.nodes, plan.num_matchings), jnp.float32)
    B, S = args.batch_per_node, args.seq

    def abs_batch(nodes):
        return {
            "tokens": jax.ShapeDtypeStruct((nodes, B, S), jnp.int32),
            "labels": jax.ShapeDtypeStruct((nodes, B, S), jnp.int32),
        }

    # -- replicated runtime --------------------------------------------------
    _log(f"replicated runtime: nodes={args.nodes}")
    mesh_r = make_test_mesh(nodes=args.nodes, model=1)
    spec_r = dt.make_spec(mesh_r, cfg)
    params_r = jax.eval_shape(
        lambda: dt.init_stacked_params(model, spec_r, seed=0)
    )
    opt_r = jax.eval_shape(lambda: dt.init_stacked_opt_state(opt, model, spec_r))
    batch_r = abs_batch(args.nodes)
    bplan_r = dt.param_bucket_plan(model)
    leaf_bytes = bytes_model.tree_storage_bytes(abs_local)

    # faulted lanes: the same strategies re-traced with per-node
    # degradation gates — every collective/byte contract must hold
    # unchanged, because a dropped exchange still issues its ppermute
    # (only the consensus delta is gated)
    rep_variants = [(m, False) for m in REPLICATED_MODES]
    if args.faults:
        rep_variants += [(m, True) for m in REPLICATED_MODES if m != "none"]
    for mode, f_lane in rep_variants:
        if not want(mode):
            continue
        label = f"replicated/{mode}" + ("+faults" if f_lane else "")
        kwargs = dict(gossip_mode=mode, faulted=f_lane)
        lane_bits = bits_f if f_lane else bits
        step_args = (params_r, opt_r, batch_r, lane_bits)
        if mode == "static":
            kwargs["active"] = tuple(range(plan.num_matchings))
        if mode == "overlap":
            kwargs["bucket_plan"] = bplan_r
            gstate = jax.eval_shape(
                lambda: dt.init_gossip_state(plan, spec_r, bplan_r)
            )
            step_args = (params_r, opt_r, gstate, batch_r, lane_bits)
        step = dt.make_train_step(model, opt, plan, spec_r, **kwargs)
        closed = to_closed_jaxpr(step, *step_args)
        records = collect(closed)
        viols = checks.check_collective_axes(records, where=label)
        viols += checks.check_dtypes(closed, where=label)
        if mode == "none":
            for r in records:
                if r.kind == "ppermute":
                    viols.append(checks.Violation(
                        "unexpected-collective",
                        "ppermute traced in the no-gossip step",
                        label,
                    ))
        else:
            viols += checks.check_ppermutes(
                records,
                num_nodes=graph.m,
                node_axes=spec_r.node_axes,
                planned_pairs=planned_pairs,
                expect_all_planned=True,
                where=label,
            )
            # per-matching traffic: storage-dtype leaves in-step
            # (masked/static), fp32 buckets one step delayed (overlap)
            want_bytes = (
                4 * bplan_r.total_elements if mode == "overlap" else leaf_bytes
            )
            from repro.analysis.collectives import ppermute_totals

            for perm, total in ppermute_totals(records).items():
                viols += checks.check_within(
                    "replicated per_matching bytes", total, want_bytes,
                    where=label,
                )
        record_step(label, closed, records, viols)

    # -- fsdp runtime: layouts x modes ---------------------------------------
    _log(f"fsdp runtime: nodes={args.nodes} shard={args.shard}")
    mesh_f = make_test_mesh(nodes=args.nodes, model=1, shard=args.shard)
    spec_f = dt.make_spec(mesh_f, cfg)
    layouts = {
        "monolithic": fsdp.make_layout(model, spec_f),
        "streamed": fsdp.make_stream_layout(model, spec_f, scan_aware=False),
        "scan_streamed": fsdp.make_stream_layout(model, spec_f, scan_aware=True),
    }
    raw_bytes = 4 * int(
        sum(np.prod(leaf.shape) for leaf in jax.tree.leaves(abs_local))
    )
    analytic_row = bytes_model.fsdp_bytes_row(
        bplan=layouts["monolithic"].plan,
        gplan=layouts["streamed"].plan,
        splan=layouts["scan_streamed"].plan,
        shard=args.shard,
        arch=args.arch,
        raw_param_bytes=raw_bytes,
    )
    report["analytic_row"] = analytic_row

    # committed-artifact cross-check (only meaningful on the smoke cfg)
    if args.preset == "tiny" and os.path.exists(args.artifact):
        with open(args.artifact) as f:
            rows = json.load(f).get("fsdp", [])
        match = [
            r for r in rows
            if r["arch"] == args.arch and r["shard"] == args.shard
        ]
        if match:
            report["artifact"]["row"] = match[0]
            viols = checks.cross_check_artifact(
                analytic_row, match[0], where="artifact"
            )
            report["artifact"]["violations"] = [v.to_json() for v in viols]
            all_violations.extend(viols)
            _log(
                f"  artifact row ({args.arch}, shard={args.shard}): "
                f"{len(viols)} violations"
            )
        else:
            _log(
                f"  artifact has no ({args.arch}, shard={args.shard}) row — "
                "cross-check skipped"
            )

    batch_f = abs_batch(args.nodes)
    for lname in args.layouts:
        layout = layouts[lname]
        ps = jax.eval_shape(lambda: fsdp.init_fsdp_params(model, layout, seed=0))
        st = jax.eval_shape(lambda: fsdp.init_fsdp_opt_state(opt, layout))
        fsdp_variants = [(m, False) for m in FSDP_MODES]
        if args.faults:
            fsdp_variants += [(m, True) for m in FSDP_MODES if m != "none"]
        for mode, f_lane in fsdp_variants:
            if not want(mode):
                continue
            label = f"fsdp/{lname}/{mode}" + ("+faults" if f_lane else "")
            step = fsdp.make_fsdp_train_step(
                model, opt, plan, spec_f, layout, gossip_mode=mode,
                faulted=f_lane,
            )
            lane_bits = bits_f if f_lane else bits
            step_args = (ps, st, batch_f, lane_bits)
            if mode == "overlap":
                gstate = jax.eval_shape(
                    lambda: fsdp.init_fsdp_gossip_state(layout)
                )
                step_args = (ps, st, gstate, batch_f, lane_bits)
            closed = to_closed_jaxpr(step, *step_args)
            records = collect(closed)
            viols = checks.check_collective_axes(records, where=label)
            viols += checks.check_dtypes(closed, where=label)
            viols += checks.check_bytes_fsdp(
                records, analytic_row, layout_kind=lname,
                gossip=mode != "none", where=label,
            )
            max_fp = None
            if mode == "none":
                # ladder bound on the gossip-free trace only: the Pallas
                # gossip-axpy kernel pads resident shards to 256k tiles
                max_fp = max_fp_intermediate(closed, ())
                viols += checks.check_memory_ladder(
                    max_fp[0], layout, where=label
                )
                for r in records:
                    if r.kind == "ppermute":
                        viols.append(checks.Violation(
                            "unexpected-collective",
                            "ppermute traced in the no-gossip step", label,
                        ))
            else:
                viols += checks.check_ppermutes(
                    records,
                    num_nodes=graph.m,
                    node_axes=spec_f.node_axes,
                    planned_pairs=planned_pairs,
                    expect_all_planned=True,
                    where=label,
                )
            # jaxpr-derived resident bytes: the step's leading invars are
            # the (nodes, S, slice) param bucket shards
            nb = layout.plan.num_buckets
            pinvars = closed.jaxpr.invars[:nb]
            if all(len(v.aval.shape) == 3 for v in pinvars):
                got = 4 * sum(int(v.aval.shape[2]) for v in pinvars)
                viols += checks.check_within(
                    "per_device_param_bytes", got,
                    analytic_row["per_device_param_bytes"], where=label,
                )
            else:
                viols.append(checks.Violation(
                    "bytes-mismatch",
                    "param bucket invars not (nodes, S, slice)-shaped — "
                    "cannot derive per-device bytes", label,
                ))
            record_step(label, closed, records, viols,
                        max_fp=max_fp)

    # -- serve steps: dtype lint (GSPMD-partitioned, no shard_map) -----------
    _log("serve steps: prefill/decode dtype lint")
    mesh_s = make_mesh((1, 1), ("data", "model"))
    rules = shd.serve_rules(mesh_s, cfg)
    max_len = args.seq + 16
    caches = sv.abstract_caches(model, B, max_len)
    tokens = jax.ShapeDtypeStruct((B, args.seq), jnp.int32)
    tok1 = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    prefill = sv.make_prefill_step(model, rules, max_len=max_len)
    decode = sv.make_decode_step(model, rules, max_len=max_len)
    for label, fn, fargs in (
        ("serve/prefill", lambda p, t, c: prefill(p, t, c),
         (abs_local, tokens, caches)),
        ("serve/decode", decode, (abs_local, tok1, caches, pos)),
    ):
        closed = to_closed_jaxpr(fn, *fargs)
        records = collect(closed)
        viols = checks.check_collective_axes(records, where=label)
        viols += checks.check_dtypes(closed, where=label)
        record_step(label, closed, records, viols)

    return emit()


if __name__ == "__main__":
    sys.exit(main())
