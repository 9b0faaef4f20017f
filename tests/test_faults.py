"""Fault-injection layer suite (``repro.faults``, docs/fault_model.md).

Covers the three degradation guarantees end to end:

* **Schedule determinism + symmetry** — a seeded FaultSchedule is
  exactly reproducible and every link-drop mask is symmetric across
  its matching edge, so each sampled step's *effective* mixing matrix
  stays symmetric and doubly stochastic. A deliberately-broken
  drop-propagation (the mutation test) must be caught by the
  ``check_degraded_mixing`` gate — consensus mass leaks otherwise.
* **Runtime parity** — an empty fault schedule (all-ones gates)
  through the ``faulted=True`` step builders is bit-identical to the
  default builders (zero-fault parity), and gossip under real drops
  matches the dense effective-W oracle.
* **Chaos smoke** — the driver under drops + a simulated crash leaves
  a restorable checkpoint history; ``--resume auto`` resumes from the
  newest complete step and the resumed trajectory matches the
  uninterrupted same-seed run.

Multi-device bodies run in subprocesses (XLA host device count must be
set before jax initializes), like tests/test_gossip_parity.py.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.analysis import schedule as schedule_checks
from repro.core import (
    effective_activation_probs,
    named_graph,
    plan_matcha,
)
from repro.faults import (
    FaultSpec,
    effective_mixing_matrix,
    make_fault_schedule,
    verify_degraded_plan,
)
from repro.faults import model as fault_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _plan(m=8, cb=0.5):
    return plan_matcha(named_graph("ring", m, seed=3), cb, budget_steps=200)


def run_sub(body: str, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(body)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert res.returncode == 0, f"stderr:\n{res.stderr[-4000:]}"
    return res.stdout


# ---------------------------------------------------------------------------
# FaultSchedule: determinism, symmetry, validation
# ---------------------------------------------------------------------------
def test_schedule_deterministic_and_edge_symmetric():
    plan = _plan()
    spec = FaultSpec(p_drop=0.3, straggler_prob=0.2, seed=11)
    a = make_fault_schedule(plan, 40, spec)
    b = make_fault_schedule(plan, 40, spec)
    np.testing.assert_array_equal(a.link_masks, b.link_masks)
    np.testing.assert_array_equal(a.delays, b.delays)
    assert not a.empty
    # a different seed draws different faults (overwhelmingly likely
    # over 40 x M x m Bernoullis at p=0.3)
    c = make_fault_schedule(
        plan, 40, FaultSpec(p_drop=0.3, straggler_prob=0.2, seed=12)
    )
    assert not np.array_equal(a.link_masks, c.link_masks)
    # edge symmetry: the gate at a node equals the gate at its partner
    # for every matching at every step — the both-endpoints guarantee
    perms = np.asarray(plan.permutations)
    for k in range(a.num_iterations):
        for j in range(a.num_matchings):
            np.testing.assert_array_equal(
                a.link_masks[k, j], a.link_masks[k, j][perms[j]],
                err_msg=f"asymmetric gate at step {k} matching {j}",
            )


def test_empty_spec_is_identity():
    plan = _plan()
    spec = FaultSpec()
    assert spec.empty and not spec.has_link_faults
    sched = make_fault_schedule(plan, 10, spec)
    assert sched.empty
    row = np.ones(plan.num_matchings, dtype=np.float32)
    bits = sched.node_bits(row, 0)
    assert bits.shape == (plan.graph.m, plan.num_matchings)
    np.testing.assert_array_equal(bits, np.ones_like(bits))
    assert sched.max_delay(0) == 0.0


def test_fault_spec_validates_at_the_edges():
    for bad in (float("nan"), -0.1, 1.5):
        with pytest.raises(ValueError, match="p_drop"):
            FaultSpec(p_drop=bad)
        with pytest.raises(ValueError, match="straggler_prob"):
            FaultSpec(straggler_prob=bad)
    with pytest.raises(ValueError, match="straggler_units"):
        FaultSpec(straggler_units=float("nan"))
    with pytest.raises(ValueError, match="crash_at_step"):
        FaultSpec(crash_at_step=-7)


# ---------------------------------------------------------------------------
# Degraded mixing: doubly stochastic W, and the gate that proves it
# ---------------------------------------------------------------------------
def test_effective_w_symmetric_doubly_stochastic():
    plan = _plan()
    sched = make_fault_schedule(plan, 50, FaultSpec(p_drop=0.4, seed=3))
    topo = plan.schedule(50, seed=3)
    m = plan.graph.m
    ones = np.ones(m)
    saw_drop = False
    for k in range(50):
        bits = sched.node_bits(topo.activations[k], k)
        saw_drop = saw_drop or sched.dropped_links(topo.activations[k], k) > 0
        W = effective_mixing_matrix(
            np.asarray(plan.permutations), plan.alpha, bits
        )
        np.testing.assert_allclose(W, W.T, atol=1e-12)
        np.testing.assert_allclose(W @ ones, ones, atol=1e-12)
    assert saw_drop, "p_drop=0.4 over 50 steps never dropped a link"


def test_mutation_broken_renormalization_is_caught(monkeypatch):
    """Mutation test for the CI gate: if drops stop propagating to the
    partner endpoint (one side keeps mixing, the other does not), the
    effective W loses symmetry and leaks consensus mass — and
    ``check_degraded_mixing`` must say so."""
    plan = _plan()
    # the clean gate passes first (so the mutation below is what flips it)
    assert schedule_checks.check_degraded_mixing(plan, p_drop=0.4) == []
    monkeypatch.setattr(
        fault_model, "_propagate_drop_to_partner",
        lambda dropped, permutations: dropped,     # no propagation
    )
    viols = schedule_checks.check_degraded_mixing(plan, p_drop=0.4)
    assert [v.name for v in viols] == ["degraded-w-not-doubly-stochastic"]
    assert "consensus mass" in viols[0].detail


# ---------------------------------------------------------------------------
# Spectral gate under faults
# ---------------------------------------------------------------------------
def test_effective_activation_probs():
    plan = _plan()
    p_eff = effective_activation_probs(plan, 0.25)
    np.testing.assert_allclose(p_eff, plan.probabilities * 0.75)
    # accepts anything with a p_drop attribute
    np.testing.assert_allclose(
        effective_activation_probs(plan, FaultSpec(p_drop=0.25)), p_eff
    )
    for bad in (float("nan"), -0.5, 2.0):
        with pytest.raises(ValueError, match="p_drop"):
            effective_activation_probs(plan, bad)


def test_check_faulted_spectral_violations_only_at_total_loss():
    plan = _plan()
    assert schedule_checks.check_faulted_spectral(plan, 0.1) == []
    names = [
        v.name for v in schedule_checks.check_faulted_spectral(plan, 1.0)
    ]
    assert names == [
        "faulted-support-disconnected", "faulted-rho-not-contractive",
    ]


def test_verify_degraded_plan_strict_raises():
    plan = _plan()
    rho, problems = verify_degraded_plan(plan, FaultSpec(p_drop=0.2))
    assert problems == [] and rho < 1.0
    with pytest.raises(ValueError, match="not contractive"):
        verify_degraded_plan(plan, FaultSpec(p_drop=1.0), strict=True)


def test_plan_matcha_rejects_bad_budget_and_probs():
    g = named_graph("ring", 8, seed=3)
    for bad in (float("nan"), 0.0, -0.5, 1.5):
        with pytest.raises(ValueError, match="comm_budget"):
            plan_matcha(g, bad)
    import dataclasses

    plan = _plan()
    poisoned = np.array(plan.probabilities)
    poisoned[0] = float("nan")
    with pytest.raises(ValueError, match="probabilities"):
        dataclasses.replace(plan, probabilities=poisoned)


# ---------------------------------------------------------------------------
# Runtime: zero-fault parity + gossip-under-drops oracle (subprocess)
# ---------------------------------------------------------------------------
def test_zero_fault_parity_bitwise():
    """faulted=True with all-ones gate rows traces the degraded path,
    but with no faults injected its trajectory must be bit-identical
    to the default builders — replicated and fsdp."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import get_smoke_config
        from repro.core import named_graph, plan_matcha
        from repro.data.pipeline import DecentralizedBatches
        from repro.dist import decen_train as dt, fsdp, sharding as shd
        from repro.launch.mesh import make_test_mesh
        from repro.models.transformer import Model
        from repro.optim.optimizers import sgd

        cfg = get_smoke_config("internlm2_1_8b")
        model = Model(cfg)
        plan = plan_matcha(named_graph("ring", 4, seed=3), 0.5,
                           budget_steps=200)
        sched = plan.schedule(3, seed=0)

        def run(builder_kwargs, make_step, init, steps=3, shard=1):
            opt = sgd(0.1, momentum=0.9)
            params, opt_state, spec, extra = init(opt)
            data = DecentralizedBatches(cfg, 4, 2, 32, seed=0)
            it = iter(data)
            step = make_step(opt, spec, extra, **builder_kwargs)
            faulted = builder_kwargs.get("faulted", False)
            with jax.set_mesh(spec.mesh):
                for k in range(steps):
                    row = sched.activations[k].astype(np.float32)
                    bits = jnp.asarray(
                        np.broadcast_to(row, (4, plan.num_matchings)).copy()
                        if faulted else row
                    )
                    params, opt_state, losses, _ = step(
                        params, opt_state, next(it), bits
                    )
            return jax.device_get(params)

        # replicated masked
        def init_rep(opt):
            mesh = make_test_mesh(nodes=4, model=1)
            spec = dt.make_spec(mesh, cfg)
            p = dt.init_stacked_params(model, spec, seed=0)
            s = dt.init_stacked_opt_state(opt, model, spec)
            pspecs = dt.stacked_param_shardings(model, spec)
            p = jax.device_put(p, shd.named_shardings(pspecs, mesh))
            return p, s, spec, None

        def mk_rep(opt, spec, extra, **kw):
            return dt.make_train_step(model, opt, plan, spec,
                                      gossip_mode="masked", **kw)

        base = run({}, mk_rep, init_rep)
        gated = run({"faulted": True}, mk_rep, init_rep)
        for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(gated)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("replicated parity OK")

        # fsdp sequential, shard=2
        def init_fsdp(opt):
            mesh = make_test_mesh(nodes=4, model=1, shard=2)
            spec = dt.make_spec(mesh, cfg)
            layout = fsdp.make_layout(model, spec)
            p = fsdp.init_fsdp_params(model, layout, seed=0)
            s = fsdp.init_fsdp_opt_state(opt, layout)
            pspecs = fsdp.fsdp_param_pspecs(spec, layout)
            with jax.set_mesh(mesh):
                p = jax.device_put(p, shd.named_shardings(pspecs, mesh))
            return p, s, spec, layout

        def mk_fsdp(opt, spec, layout, **kw):
            return fsdp.make_fsdp_train_step(
                model, opt, plan, spec, layout,
                gossip_mode="sequential", **kw)

        base = run({}, mk_fsdp, init_fsdp)
        gated = run({"faulted": True}, mk_fsdp, init_fsdp)
        for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(gated)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("fsdp parity OK")
    """)
    assert "replicated parity OK" in out and "fsdp parity OK" in out


def test_gossip_under_drops_matches_dense_oracle():
    """Masked gossip fed per-node effective rows == mix_dense with the
    fault model's effective mixing matrix, for every sampled step."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import paper_figure1_graph, plan_matcha
        from repro.dist.gossip import (
            NodeAxisInfo, mix_dense, mix_matchings_masked,
        )
        from repro.faults import (
            FaultSpec, effective_mixing_matrix, make_fault_schedule,
        )
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh(nodes=8, model=1)
        plan = plan_matcha(paper_figure1_graph(), 0.5, budget_steps=400)
        steps = 8
        topo = plan.schedule(steps, seed=3)
        fsched = make_fault_schedule(
            plan, steps, FaultSpec(p_drop=0.35, seed=9))
        info = NodeAxisInfo(axis_names=("data",), num_nodes=8)
        x = {"w": jax.random.normal(jax.random.key(0), (8, 16, 8)),
             "b": jax.random.normal(jax.random.key(1), (8, 129))}
        specs = jax.tree.map(lambda _: P("data"), x)

        def body(xs, ebits):
            local = jax.tree.map(lambda a: a[0], xs)
            mixed = mix_matchings_masked(
                local, plan.alpha, plan.permutations, ebits[0], info)
            return jax.tree.map(lambda a: a[None], mixed)

        total_dropped = 0
        for k in range(steps):
            ebits = fsched.node_bits(topo.activations[k], k)   # (8, M)
            total_dropped += fsched.dropped_links(topo.activations[k], k)
            with jax.set_mesh(mesh):
                f = jax.shard_map(body, mesh=mesh,
                                  in_specs=(specs, P("data")),
                                  out_specs=specs, axis_names={"data"},
                                  check_vma=False)
                got = jax.jit(f)(x, jnp.asarray(ebits))
            W = effective_mixing_matrix(
                np.asarray(plan.permutations), plan.alpha, ebits)
            want = mix_dense(x, jnp.asarray(W))
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5,
                    err_msg=f"step {k}")
        assert total_dropped > 0, "no drops sampled at p_drop=0.35"
        print(f"oracle OK ({total_dropped} dropped exchanges)")
    """)
    assert "oracle OK" in out


# ---------------------------------------------------------------------------
# Chaos smoke: drops + crash + resume through the real driver
# ---------------------------------------------------------------------------
def _train(*extra, expect_rc=0, timeout=900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--preset", "tiny",
         "--nodes", "4", "--graph", "ring", "--steps", "8",
         "--batch-per-node", "2", "--seq", "32", "--seed", "1",
         "--p-drop", "0.25", "--fault-seed", "5", *extra],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )
    assert res.returncode == expect_rc, (
        f"rc={res.returncode} (want {expect_rc})\n"
        f"stdout:\n{res.stdout[-2000:]}\nstderr:\n{res.stderr[-3000:]}"
    )
    return res.stdout


def _csv_rows(path):
    import csv

    with open(path, newline="") as f:
        return {int(r["step"]): r for r in csv.DictReader(f)}


def test_chaos_smoke_crash_resume_matches_uninterrupted(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    csv_a = str(tmp_path / "a.csv")
    csv_b = str(tmp_path / "b.csv")

    # run A: uninterrupted faulted run
    out_a = _train("--csv", csv_a)

    # run B: same seeds, crash after step 4 (checkpoint landed at step 3)
    out_b = _train(
        "--ckpt-dir", ckpt, "--ckpt-every", "3",
        "--crash-at-step", "4", expect_rc=1,
    )
    assert "simulated crash after completing step 4" in out_b
    assert os.path.isdir(os.path.join(ckpt, "step_00000003"))
    # same seed => identical pre-crash trajectory (the step-0 log line
    # prints loss + consensus to full working precision)
    line_a = [l for l in out_a.splitlines() if l.startswith("step    0")]
    line_b = [l for l in out_b.splitlines() if l.startswith("step    0")]
    assert line_a == line_b and line_a

    # run B resumed: must pick up the newest complete checkpoint and
    # land on run A's trajectory
    out_r = _train(
        "--ckpt-dir", ckpt, "--ckpt-every", "3",
        "--resume", "auto", "--csv", csv_b,
    )
    assert f"resumed from {os.path.join(ckpt, 'step_00000003')}" in out_r
    rows_a, rows_b = _csv_rows(csv_a), _csv_rows(csv_b)
    final = max(rows_a)
    assert final in rows_b, f"resumed run logged no step-{final} row"
    for col in ("loss", "consensus"):
        np.testing.assert_allclose(
            float(rows_b[final][col]), float(rows_a[final][col]),
            rtol=1e-5, atol=1e-7,
            err_msg=f"resumed {col} diverged from uninterrupted run",
        )
    # the final checkpoint of the resumed run is itself restorable
    from repro.checkpoint import ckpt as ckpt_lib

    resolved = ckpt_lib.find_resumable(ckpt)
    assert resolved is not None and resolved.endswith("step_00000008")
