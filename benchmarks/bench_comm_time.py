"""Paper Fig. 1 + headline claim: per-node communication time reduction.

Fig 1: per-node expected communication time under MATCHA vs vanilla on
the 8-node base graph — critical links (degree-1 node 4) keep their
communication; the busiest node (degree-5 node 1) is relieved.

Headline ("50x reduction in communication delay per iteration on
CIFAR-100"): at CB=0.02 the per-iteration expected delay is
CB * M_vanilla vs M_vanilla -> 1/CB = 50x.

Execution-strategy cost model: sequential gossip (masked/static) pays
``comm(k) + compute`` per step, the overlapped one-step-delayed mode
pays ``max(comm(k), compute)`` — the exchange hides behind the next
step's fwd/bwd. Both are reported per comm budget and the full result
set lands in ``BENCH_comm_time.json`` (the CI smoke artifact).

Measured section (``repro.telemetry``): alongside the analytic model,
``run()`` spawns a worker subprocess (the 8-device CPU mesh needs
XLA_FLAGS set before jax init, like ``bench_convergence``) that trains
the smoke model for a few fenced steps in the sequential AND overlap
strategies and probes each matching's ppermute as its own fenced
executable. The artifact gains a ``measured`` object
(``measured_step_ms`` per strategy, expected ``measured_comm_ms``, and
per-matching mean/p50/p95), ``step_time_overlap.csv`` gains measured
columns next to the modeled units, and a tolerant cross-check asserts
the measured sequential/overlap ratio is directionally consistent with
the model. Measured numbers are machine-dependent wall-clock: they are
NOT gated by ``--compare`` (only the byte metrics are) and the
directional check carries a generous tolerance. The worker's trace
lands in ``benchmarks/results/trace/`` (the CI bench-smoke upload).
Disable with ``--no-measured`` / ``run(measured=False)``.

Degraded-mode section (``docs/fault_model.md``): a ``faults`` table at
the measured budget reports, per injected drop rate p_drop in
{0, 0.1, 0.3}, the exact contraction factor at the faulted activation
probabilities p_eff = p * (1 - p_drop), the (unchanged) issued comm
units, the expected surviving exchanges, and the measured masked-mode
step time under a seeded FaultSchedule. Analytic columns are gated by
deterministic checks (rho monotone in p_drop, < 1 throughout); the
measured column is directional wall-clock only and — like every
measured number — never enters the ``--compare`` regression fields.

FSDP composition: the sharded-replica mode (``repro.dist.fsdp``) keeps
1/S of every fp32 bucket per device and gossips the shards directly, so
per-device param bytes AND per-matching gossip bytes both shrink by the
shard factor — the ``fsdp`` section of the artifact tabulates both from
the real bucket layout of the smoke model, and the smoke job asserts
the shrink. Each row also records *peak transient* bytes per device —
the largest full-size view the fwd/bwd materializes: the whole padded
replica for the monolithic gather vs the largest layer group for
``--stream-layers`` (``plan_group_buckets`` over
``Model.param_group_specs``) — and the smoke job asserts the streamed
peak is strictly below the monolithic one at every shard factor. A
second table deepens the dbrx smoke config to a scanned 8-layer stack
and adds ``peak_transient_bytes_scan_streamed`` (the scan-aware plan's
per-layer-row peak) plus ``num_scan_iterations``; for every scanned
row the scan-streamed peak must sit strictly below the stack-at-once
streamed peak.
"""
from __future__ import annotations

import csv
import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmarks.artifacts import RESULTS_DIR, comm_time_artifact, trace_dir
from repro.core import paper_figure1_graph, plan_matcha, plan_vanilla

COMPUTE_UNITS = 1.0      # the paper's linear delay model: 1 unit of compute

MEASURED_CB = 0.5        # the comm budget the measured section runs at
MEASURED_STEPS = 8       # fenced steps per strategy (after 2 warmup)
# Directional-consistency tolerance: the model says overlap <= sequential
# per step; measured CPU wall-clock is noisy and the CPU backend hides
# little latency, so only a large inversion fails the check.
MEASURED_RATIO_SLACK = 1.25


def step_time_model(plan, *, steps: int = 2000, seed: int = 0) -> dict:
    """Expected per-iteration step time over a drawn schedule, under the
    linear delay model, for both execution strategies."""
    sched = plan.schedule(steps, seed=seed)
    comm = sched.activations.sum(axis=1).astype(np.float64)
    sequential = comm + COMPUTE_UNITS
    overlapped = np.maximum(comm, COMPUTE_UNITS)
    return dict(
        expected_comm=float(comm.mean()),
        sequential=float(sequential.mean()),
        overlapped=float(overlapped.mean()),
    )


def fsdp_bytes_table(
    arch: str = "internlm2_1_8b", shard_factors=(1, 2, 4), *,
    num_layers: int = 0, label: str = "",
) -> list:
    """Per-device param bytes, per-matching gossip bytes and peak
    transient (fwd/bwd view) bytes at each shard factor, from the
    actual fsdp bucket layouts (``pad_to=S``) of the smoke model —
    abstract shapes only, nothing is allocated.

    Each row carries two streamed peaks: ``peak_transient_bytes_streamed``
    (largest layer group, stack-at-once scan gathers) and
    ``peak_transient_bytes_scan_streamed`` (scan-aware plan: a scanned
    segment's peak is one *layer row*, not the stack).
    ``num_layers``/``label`` deepen the smoke config so a scanned stack
    (``repeats >= SCAN_THRESHOLD``) actually forms and report it under a
    distinct arch label.

    The byte math lives in ``repro.analysis.bytes_model`` — the same
    formulas the static analyzer cross-checks against traced jaxprs, so
    the artifact is verified, not merely asserted."""
    from repro.analysis.bytes_model import fsdp_bytes_rows

    return fsdp_bytes_rows(
        arch, shard_factors, num_layers=num_layers, label=label
    )


def measured_section(
    out_dir: str, *, steps: int = MEASURED_STEPS, cb: float = MEASURED_CB
) -> dict:
    """Run the measured worker in a subprocess (the 8-device CPU mesh
    needs XLA_FLAGS before jax init; this process may already hold a
    1-device jax). Returns the worker's ``measured`` payload."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.setdefault("PYTHONPATH", "src")
    res = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_comm_time",
         "--worker", "--steps", str(steps), "--cb", str(cb),
         "--out", out_dir],
        capture_output=True, text=True, env=env, timeout=1800,
    )
    if res.returncode != 0:
        raise RuntimeError(
            f"measured comm-time worker failed:\n{res.stderr[-3000:]}"
        )
    return json.loads(res.stdout.splitlines()[-1])


def _measured_worker(out_dir: str, steps: int, cb: float) -> dict:
    """Measured per-strategy step times + per-matching probes on the
    smoke model (runs on the worker's 8-device mesh; prints JSON)."""
    import jax
    import jax.numpy as jnp

    from repro.configs.registry import get_smoke_config
    from repro.data.pipeline import DecentralizedBatches
    from repro.dist import decen_train as dt
    from repro.dist import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import Model
    from repro.optim.optimizers import sgd
    from repro.telemetry import StepTimer, TraceRecorder
    from repro.telemetry.probes import measure_matchings, summarize_ms

    warmup = 2
    g = paper_figure1_graph()
    cfg = get_smoke_config("internlm2_1_8b")
    model = Model(cfg)
    mesh = make_mesh((8, 1), ("data", "model"))
    spec = dt.make_spec(mesh, cfg, multi_pod=False)
    plan = plan_matcha(g, cb, budget_steps=800)
    sched = plan.schedule(steps + warmup, seed=1)
    recorder = TraceRecorder(
        meta=dict(bench="comm_time", arch=cfg.name, cb=cb, steps=steps)
    )
    timer = StepTimer(recorder)

    abs_local = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    elems = int(sum(
        np.prod(l.shape) for l in jax.tree.leaves(abs_local)
    ))
    out = dict(cb=cb, steps=steps, nodes=8, arch=cfg.name)
    with jax.set_mesh(mesh):
        pm = measure_matchings(
            plan, spec, per_node_elements=elems, timer=timer, iters=5
        )
        out["per_matching"] = [
            {k: round(v, 4) if isinstance(v, float) else v
             for k, v in r.items()}
            for r in pm
        ]
        # expected measured comm per iteration: each matching's measured
        # mean weighted by its activation probability (the measured
        # analogue of the model's expected_comm units)
        probs = np.asarray(plan.probabilities, dtype=np.float64)
        out["measured_comm_ms"] = round(float(sum(
            probs[r["matching"]] * r["mean_ms"] for r in pm
        )), 4)

        for mode, label in (("masked", "sequential"), ("overlap", "overlap")):
            opt = sgd(0.1, momentum=0.9)
            params = dt.init_stacked_params(model, spec, seed=0)
            opt_state = dt.init_stacked_opt_state(opt, model, spec)
            pspecs = dt.stacked_param_shardings(model, spec)
            params = jax.device_put(params, shd.named_shardings(pspecs, mesh))
            data = DecentralizedBatches(cfg, 8, 4, 64, seed=0)
            it = iter(data)
            gstate = None
            if mode == "overlap":
                bplan = dt.param_bucket_plan(model)
                gstate = dt.init_gossip_state(plan, spec, bplan)
                step = dt.make_train_step(
                    model, opt, plan, spec, gossip_mode=mode,
                    bucket_plan=bplan,
                )
            else:
                step = dt.make_train_step(
                    model, opt, plan, spec, gossip_mode=mode
                )
            samples = []
            for k in range(steps + warmup):
                bits = jnp.asarray(sched.activations[k].astype(np.float32))
                batch = next(it)
                t0 = time.perf_counter()
                with timer.phase("step", cat="step", step=k,
                                 mode=label) as sp:
                    if mode == "overlap":
                        params, opt_state, gstate, losses, _ = step(
                            params, opt_state, gstate, batch, bits
                        )
                    else:
                        params, opt_state, losses, _ = step(
                            params, opt_state, batch, bits
                        )
                    sp.fence((params, losses))
                if k >= warmup:        # first steps pay compilation
                    samples.append((time.perf_counter() - t0) * 1e3)
            s = summarize_ms(samples)
            out[label] = dict(
                measured_step_ms=round(s["mean_ms"], 4),
                p50_ms=round(s["p50_ms"], 4),
                p95_ms=round(s["p95_ms"], 4),
                n=s["n"],
            )

        # degraded-mode wall clock: the masked strategy re-run under a
        # seeded FaultSchedule (per-node gate rows). Every ppermute is
        # still issued — drops only gate the consensus delta — so these
        # times are directional context next to the fault-free
        # sequential row, never a regression gate.
        from repro.faults import FaultSpec, make_fault_schedule

        out["faulted"] = []
        for pd in (0.1, 0.3):
            opt = sgd(0.1, momentum=0.9)
            params = dt.init_stacked_params(model, spec, seed=0)
            opt_state = dt.init_stacked_opt_state(opt, model, spec)
            pspecs = dt.stacked_param_shardings(model, spec)
            params = jax.device_put(params, shd.named_shardings(pspecs, mesh))
            data = DecentralizedBatches(cfg, 8, 4, 64, seed=0)
            it = iter(data)
            fsched = make_fault_schedule(
                plan, steps + warmup, FaultSpec(p_drop=pd, seed=2)
            )
            step = dt.make_train_step(
                model, opt, plan, spec, gossip_mode="masked", faulted=True
            )
            samples = []
            dropped = 0
            for k in range(steps + warmup):
                bits = jnp.asarray(
                    fsched.node_bits(sched.activations[k], k)
                )
                batch = next(it)
                t0 = time.perf_counter()
                with timer.phase("step", cat="step", step=k,
                                 mode=f"faulted_p{pd}") as sp:
                    params, opt_state, losses, _ = step(
                        params, opt_state, batch, bits
                    )
                    sp.fence((params, losses))
                if k >= warmup:
                    samples.append((time.perf_counter() - t0) * 1e3)
                    dropped += fsched.dropped_links(sched.activations[k], k)
            s = summarize_ms(samples)
            out["faulted"].append(dict(
                p_drop=pd,
                measured_step_ms=round(s["mean_ms"], 4),
                p50_ms=round(s["p50_ms"], 4),
                p95_ms=round(s["p95_ms"], 4),
                n=s["n"],
                dropped_exchanges=int(dropped),
            ))
    jsonl_path, chrome_path = recorder.flush(trace_dir(out_dir))
    out["trace"] = dict(events=jsonl_path, chrome=chrome_path,
                        num_events=len(recorder.events()))
    return out


def per_node_comm_time(plan) -> np.ndarray:
    """Expected units each node spends communicating per iteration:
    sum over matchings containing the node of p_j (one unit each)."""
    m = plan.graph.m
    out = np.zeros(m)
    for j, sg in enumerate(plan.matchings):
        p = plan.probabilities[j]
        for a, b in sg.edges:
            out[a] += p
            out[b] += p
    return out


def run(out_dir: str = RESULTS_DIR, measured: bool | None = None):
    """Full bench. ``measured=False`` skips the wall-clock worker
    subprocess (the analytic model and byte tables still run)."""
    if measured is None:
        measured = True
    t0 = time.time()
    g = paper_figure1_graph()
    van = plan_vanilla(g)
    # plan each budget once; the per-node table, the step-time table and
    # the headline check all reuse the same plans
    plans = {
        cb: plan_matcha(g, cb, budget_steps=1500)
        for cb in (0.02, 0.1, 0.5, 0.75, 1.0)
    }
    rows = []
    for cb in (0.02, 0.1, 0.5):
        mp = plans[cb]
        tv = per_node_comm_time(van)
        tm = per_node_comm_time(mp)
        for node in range(g.m):
            rows.append(dict(
                cb=cb, node=node, degree=int(g.degrees()[node]),
                t_vanilla=round(float(tv[node]), 3),
                t_matcha=round(float(tm[node]), 3),
            ))
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "per_node_comm_time.csv"), "w",
              newline="") as f:
        w = csv.DictWriter(f, fieldnames=list(rows[0]))
        w.writeheader()
        w.writerows(rows)

    # execution strategies: sequential comm+compute vs overlapped max()
    step_rows = []
    for cb, mp in plans.items():
        st = step_time_model(mp)
        step_rows.append(dict(cb=cb, **{k: round(v, 4) for k, v in st.items()}))

    # measured wall-clock next to the modeled units (worker subprocess;
    # fills only the row at MEASURED_CB — measuring every budget would
    # recompile two strategies per row for no additional signal)
    meas = measured_section(out_dir) if measured else None
    measured_cols = (
        "measured_step_sequential_ms", "measured_step_overlap_ms",
        "measured_comm_ms",
    )
    for r in step_rows:
        if meas is not None and r["cb"] == meas["cb"]:
            r["measured_step_sequential_ms"] = (
                meas["sequential"]["measured_step_ms"])
            r["measured_step_overlap_ms"] = (
                meas["overlap"]["measured_step_ms"])
            r["measured_comm_ms"] = meas["measured_comm_ms"]
        else:
            for c in measured_cols:
                r[c] = ""
    with open(os.path.join(out_dir, "step_time_overlap.csv"), "w",
              newline="") as f:
        w = csv.DictWriter(f, fieldnames=list(step_rows[0]))
        w.writeheader()
        w.writerows(step_rows)

    checks = []
    for r in step_rows:
        if r["cb"] >= 0.5:
            checks.append((
                f"CB={r['cb']}: overlapped {r['overlapped']:.2f}u < "
                f"sequential {r['sequential']:.2f}u",
                r["overlapped"] < r["sequential"],
            ))
    # Fig-1 claims at CB=0.5
    half = {r["node"]: r for r in rows if r["cb"] == 0.5}
    # the degree-1 node (4) keeps most of its communication (critical link)
    keep_ratio = half[4]["t_matcha"] / max(half[4]["t_vanilla"], 1e-9)
    checks.append(("critical degree-1 node keeps >=60% of its comm",
                   keep_ratio >= 0.6))
    # the busiest node's comm is cut to ~<=60%
    busy_ratio = half[1]["t_matcha"] / max(half[1]["t_vanilla"], 1e-9)
    checks.append(("busiest node (deg 5) cut to <= 60%", busy_ratio <= 0.6))
    # headline: per-iteration delay ratio at CB=0.02 ~= 50x
    mp = plans[0.02]
    ratio = van.vanilla_comm_units / max(mp.expected_comm_units, 1e-9)
    checks.append((f"CB=0.02 delay reduction {ratio:.0f}x >= 40x", ratio >= 40))

    # fsdp composition: per-device bytes shrink by the shard factor
    # (padding to shard-divisible bucket sizes costs < 1%). The second
    # table deepens the dbrx smoke config to 8 layers so a scanned
    # stack actually forms and the scan-aware plan has a row to cut.
    fsdp_rows = fsdp_bytes_table() + fsdp_bytes_table(
        arch="dbrx_132b", num_layers=8, label="dbrx_132b_deep8"
    )
    by_key = {(r["arch"], r["shard"]): r for r in fsdp_rows}
    archs = sorted({r["arch"] for r in fsdp_rows})
    for a in archs:
        for s in (2, 4):
            for field, label in (
                ("per_device_param_bytes", "per-device param bytes"),
                ("per_matching_comm_bytes", "per-matching gossip bytes"),
            ):
                checks.append((
                    f"fsdp shard={s}: {a} {label} {by_key[a, s][field]} <= "
                    f"replica/{s} + 1% pad",
                    by_key[a, s][field] * s <= by_key[a, 1][field] * 1.01,
                ))
    # streaming: the largest layer-group view must be strictly smaller
    # than the monolithic gathered replica at every shard factor, and
    # on scanned configs the scan-aware per-layer-row peak must sit
    # strictly below the stack-at-once streamed peak
    for (a, s), r in sorted(by_key.items()):
        checks.append((
            f"stream shard={s}: {a} peak transient "
            f"{r['peak_transient_bytes_streamed']} B "
            f"({r['num_layer_groups']} groups) < monolithic "
            f"{r['peak_transient_bytes_monolithic']} B",
            r["peak_transient_bytes_streamed"]
            < r["peak_transient_bytes_monolithic"],
        ))
        if r["num_scan_iterations"]:
            checks.append((
                f"stream shard={s}: {a} scan-streamed peak "
                f"{r['peak_transient_bytes_scan_streamed']} B "
                f"({r['num_scan_iterations']} scan iterations) < streamed "
                f"{r['peak_transient_bytes_streamed']} B",
                r["peak_transient_bytes_scan_streamed"]
                < r["peak_transient_bytes_streamed"],
            ))
        else:
            # no scanned stack: the scan-aware plan must degrade to the
            # stack-at-once layout exactly
            checks.append((
                f"stream shard={s}: {a} unscanned scan-streamed peak == "
                f"streamed ({r['peak_transient_bytes_scan_streamed']} B)",
                r["peak_transient_bytes_scan_streamed"]
                == r["peak_transient_bytes_streamed"],
            ))
    # degraded-mode section (docs/fault_model.md): modeled contraction
    # + comm at injected drop rates. rho rises with p_drop (less
    # expected mixing) while the *issued* comm units are unchanged —
    # a dropped exchange still runs, only its delta is gated. The
    # measured column is directional wall-clock context and, like all
    # measured numbers, never enters REGRESSION_FIELDS.
    from repro.core.matcha import effective_activation_probs
    from repro.core.mixing import exact_rho

    mp = plans[MEASURED_CB]
    lap = [sg.laplacian() for sg in mp.matchings]
    fault_rows = []
    meas_faulted = {
        r["p_drop"]: r for r in (meas or {}).get("faulted", [])
    }
    if meas is not None:
        meas_faulted[0.0] = meas["sequential"]
    for pd in (0.0, 0.1, 0.3):
        p_eff = effective_activation_probs(mp, pd)
        row = dict(
            cb=MEASURED_CB, p_drop=pd,
            rho_faulted=round(float(exact_rho(lap, p_eff, mp.alpha)), 6),
            comm_units_issued=round(float(mp.expected_comm_units), 4),
            expected_surviving_exchanges=round(float(p_eff.sum()), 4),
        )
        mrow = meas_faulted.get(pd)
        row["measured_step_ms"] = (
            mrow["measured_step_ms"] if mrow else ""
        )
        fault_rows.append(row)
    rho_seq = [r["rho_faulted"] for r in fault_rows]
    checks.append((
        f"faults: rho monotone in p_drop {rho_seq} and < 1 throughout",
        all(a <= b + 1e-12 for a, b in zip(rho_seq, rho_seq[1:]))
        and all(r < 1.0 for r in rho_seq),
    ))
    checks.append((
        "faults: issued comm units independent of p_drop (drops gate "
        "deltas, not exchanges)",
        len({r["comm_units_issued"] for r in fault_rows}) == 1,
    ))

    # measured cross-checks: directional consistency only — wall-clock
    # magnitudes are machine-dependent and stay out of the --compare gate
    if meas is not None:
        seq_ms = meas["sequential"]["measured_step_ms"]
        ovl_ms = meas["overlap"]["measured_step_ms"]
        checks.append((
            f"measured CB={meas['cb']}: overlap {ovl_ms:.1f} ms <= "
            f"sequential {seq_ms:.1f} ms x {MEASURED_RATIO_SLACK}",
            ovl_ms <= seq_ms * MEASURED_RATIO_SLACK,
        ))
        n_match = len(plans[meas["cb"]].matchings)
        checks.append((
            f"measured: probed all {n_match} matchings",
            len(meas["per_matching"]) == n_match,
        ))
        checks.append((
            f"measured: expected comm {meas['measured_comm_ms']:.2f} ms > 0",
            meas["measured_comm_ms"] > 0,
        ))
    us = (time.time() - t0) * 1e6 / max(len(rows), 1)

    # machine-readable artifact for the CI benchmarks smoke job; the
    # measured object is additive — the --compare gate only reads the
    # byte metrics (REGRESSION_FIELDS in benchmarks/run.py)
    with open(comm_time_artifact(out_dir), "w") as f:
        json.dump(
            dict(
                per_node=rows,
                step_time=step_rows,
                fsdp=fsdp_rows,
                faults=fault_rows,
                measured=meas,
                checks=[dict(name=n, ok=bool(ok)) for n, ok in checks],
            ),
            f, indent=2,
        )
    return rows, checks, us


def build_parser():
    """CLI: the default invocation runs the full bench; ``--worker`` is
    the measured subprocess body (spawned by :func:`measured_section`,
    not for direct use)."""
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--no-measured", action="store_true",
                    help="skip the measured wall-clock worker")
    ap.add_argument("--worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--steps", type=int, default=MEASURED_STEPS)
    ap.add_argument("--cb", type=float, default=MEASURED_CB)
    ap.add_argument("--out", default=RESULTS_DIR)
    return ap


if __name__ == "__main__":
    args = build_parser().parse_args()
    if args.worker:
        payload = _measured_worker(args.out, args.steps, args.cb)
        print(json.dumps(payload))
    else:
        _, checks, _ = run(out_dir=args.out, measured=not args.no_measured)
        for name, ok in checks:
            print(("PASS " if ok else "FAIL ") + name)
