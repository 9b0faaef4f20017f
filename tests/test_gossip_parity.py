"""Execution-strategy parity suite.

Per activated schedule row, the three sequential encodings of eq. (2)-(3)
must agree — masked (traced bits), static (baked subset, including with
duplicate ids in ``active``), and the ``mix_dense`` O(m^2) oracle — on
fp32 and bf16 params, on single-axis and multi-pod ("pod","data")
meshes. The overlapped (one-step-delayed, bucketed) strategy must
reproduce the sequential gossip trajectory exactly when gradients are
zero (gossip-only), share its fixed point (the node mean), and train to
a consensus distance within 2x of masked at equal iterations.

Multi-device bodies run in subprocesses (XLA host device count must be
set before jax initializes), like tests/test_dist_multidevice.py.
"""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


def test_masked_static_dense_parity_per_schedule_row():
    """masked == static == dense oracle for every drawn schedule row,
    fp32 and bf16, with duplicate ids deduped in the static path."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import paper_figure1_graph, plan_matcha
        from repro.dist.gossip import (
            NodeAxisInfo, mix_dense, mix_matchings, mix_matchings_masked,
        )
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh(nodes=8, model=1)
        plan = plan_matcha(paper_figure1_graph(), 0.5, budget_steps=400)
        sched = plan.schedule(6, seed=3)
        info = NodeAxisInfo(axis_names=("data",), num_nodes=8)

        for dtype, tol in ((jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)):
            x = {"w": jax.random.normal(jax.random.key(0), (8, 16, 8), dtype),
                 "b": jax.random.normal(jax.random.key(1), (8, 129), dtype)}
            specs = jax.tree.map(lambda _: P("data"), x)
            for k in range(sched.num_iterations):
                active = sched.active_indices(k)
                bits = jnp.asarray(sched.activations[k].astype(np.float32))
                dup = active + active[:1]       # duplicate id: must dedupe

                def body(xs, bits):
                    local = jax.tree.map(lambda a: a[0], xs)
                    ex = lambda t: jax.tree.map(lambda a: a[None], t)
                    st = mix_matchings(local, plan.alpha, plan.permutations,
                                       dup, info)
                    mk = mix_matchings_masked(local, plan.alpha,
                                              plan.permutations, bits, info)
                    return ex(st), ex(mk)

                with jax.set_mesh(mesh):
                    f = jax.shard_map(body, mesh=mesh, in_specs=(specs, P()),
                                      out_specs=(specs, specs),
                                      axis_names={"data"},
                                      check_vma=False)
                    got_s, got_m = jax.jit(f)(x, bits)
                W = np.eye(8) - plan.alpha * sched.laplacian(k)
                want = mix_dense(x, jnp.asarray(W))
                for name, got in (("static", got_s), ("masked", got_m)):
                    for a, b in zip(jax.tree.leaves(got),
                                    jax.tree.leaves(want)):
                        np.testing.assert_allclose(
                            np.asarray(a, np.float32),
                            np.asarray(b, np.float32),
                            atol=tol, rtol=tol,
                            err_msg=f"{name} row {k} dtype {dtype}")
        print("OK")
    """)
    assert "OK" in out


def test_multipod_masked_static_dense_parity_bf16():
    """(2 pods x 4 data) collapsed node axis: all three paths agree on
    bf16 params across the pod boundary."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import plan_matcha, ring_graph
        from repro.dist.gossip import (
            NodeAxisInfo, mix_dense, mix_matchings, mix_matchings_masked,
        )
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh(nodes=8, model=1, multi_pod=True)
        plan = plan_matcha(ring_graph(8), 0.6, budget_steps=300)
        info = NodeAxisInfo(axis_names=("pod", "data"), num_nodes=8)
        active = tuple(range(plan.num_matchings))
        bits = jnp.ones((plan.num_matchings,), jnp.float32)
        x = {"w": jax.random.normal(jax.random.key(0), (8, 65), jnp.bfloat16)}
        specs = jax.tree.map(lambda _: P(("pod", "data")), x)

        def body(xs, bits):
            local = jax.tree.map(lambda a: a[0], xs)
            ex = lambda t: jax.tree.map(lambda a: a[None], t)
            st = mix_matchings(local, plan.alpha, plan.permutations,
                               active, info)
            mk = mix_matchings_masked(local, plan.alpha, plan.permutations,
                                      bits, info)
            return ex(st), ex(mk)

        with jax.set_mesh(mesh):
            f = jax.shard_map(body, mesh=mesh, in_specs=(specs, P()),
                              out_specs=(specs, specs),
                              axis_names={"pod", "data"},
                              check_vma=False)
            got_s, got_m = jax.jit(f)(x, bits)
        L = sum(m.laplacian() for m in plan.matchings)
        W = np.eye(8) - plan.alpha * L
        want = mix_dense(x, jnp.asarray(W))
        for got in (got_s, got_m):
            np.testing.assert_allclose(
                np.asarray(got["w"], np.float32),
                np.asarray(want["w"], np.float32), atol=2e-2, rtol=2e-2)
        print("OK")
    """)
    assert "OK" in out


def test_overlap_matches_sequential_gossip_and_fixed_point():
    """Gossip-only (zero grads) the delayed scheme IS sequential gossip
    shifted by one round: overlap round r+1 == masked round r, and both
    contract to the node mean (the shared fixed point)."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import paper_figure1_graph, plan_matcha
        from repro.dist import bucketing
        from repro.dist.gossip import (
            NodeAxisInfo, apply_gossip, delayed_delta,
            launch_matchings_masked, mix_matchings_masked,
        )
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh(nodes=8, model=1)
        plan = plan_matcha(paper_figure1_graph(), 1.0, budget_steps=300)
        info = NodeAxisInfo(axis_names=("data",), num_nodes=8)
        M = plan.num_matchings
        x0 = {"w": jax.random.normal(jax.random.key(0), (8, 33, 5)),
              "b": jax.random.normal(jax.random.key(1), (8, 17))}
        specs = jax.tree.map(lambda _: P("data"), x0)
        local_abs = jax.eval_shape(
            lambda t: jax.tree.map(lambda a: a[0], t), x0)
        bplan = bucketing.plan_buckets(local_abs)
        bspec = tuple(P("data") for _ in range(bplan.num_buckets))

        def overlap_round(xs, sent, recv, prev_bits, bits):
            local = jax.tree.map(lambda a: a[0], xs)
            s = tuple(a[0] for a in sent)
            r = tuple(a[0] for a in recv)
            deltas = delayed_delta(s, r, prev_bits)
            dt_tree = bucketing.unravel(bplan, deltas)
            target = jax.tree.map(
                lambda x, d: x.astype(jnp.float32) + d, local, dt_tree)
            x = apply_gossip(local, target, plan.alpha)
            new_sent = bucketing.ravel(bplan, x)
            new_recv = launch_matchings_masked(
                new_sent, bits, plan.permutations, info)
            ex = lambda t: jax.tree.map(lambda a: a[None], t)
            return (ex(x), tuple(a[None] for a in new_sent),
                    tuple(a[None] for a in new_recv))

        def masked_round(xs, bits):
            local = jax.tree.map(lambda a: a[0], xs)
            out = mix_matchings_masked(
                local, plan.alpha, plan.permutations, bits, info)
            return jax.tree.map(lambda a: a[None], out)

        ones = jnp.ones((M,), jnp.float32)
        zeros_bits = jnp.zeros((M,), jnp.float32)
        with jax.set_mesh(mesh):
            fo = jax.jit(jax.shard_map(
                overlap_round, mesh=mesh,
                in_specs=(specs, bspec, bspec, P(), P()),
                out_specs=(specs, bspec, bspec), axis_names={"data"},
                check_vma=False))
            fm = jax.jit(jax.shard_map(
                masked_round, mesh=mesh, in_specs=(specs, P()),
                out_specs=specs, axis_names={"data"},
                check_vma=False))

            K = 30
            sent = tuple(jnp.zeros((8, s), jnp.float32)
                         for s in bplan.bucket_sizes)
            recv = tuple(jnp.zeros_like(s) for s in sent)
            xo, prev_bits = x0, zeros_bits
            seq = [x0]
            xm = x0
            for _ in range(K):
                xm = fm(xm, ones)
                seq.append(xm)
            for r in range(K + 1):
                xo, sent, recv = fo(xo, sent, recv, prev_bits, ones)
                prev_bits = ones
                # overlap after r+1 rounds == sequential after r rounds
                for a, b in zip(jax.tree.leaves(xo), jax.tree.leaves(seq[r])):
                    np.testing.assert_allclose(
                        np.asarray(a), np.asarray(b), atol=1e-5,
                        err_msg=f"round {r}")
        # both contract toward the node mean (the shared fixed point):
        # the spread shrinks by >= 10x and the mean itself is preserved
        # (W is doubly stochastic, delayed or not)
        for leaf0, leafK in zip(jax.tree.leaves(x0), jax.tree.leaves(xo)):
            a0, aK = np.asarray(leaf0), np.asarray(leafK)
            mean = a0.mean(axis=0, keepdims=True)
            spread0 = np.abs(a0 - mean).max()
            spreadK = np.abs(aK - aK.mean(axis=0, keepdims=True)).max()
            assert spreadK < 0.1 * spread0, (spreadK, spread0)
            np.testing.assert_allclose(aK.mean(axis=0), a0.mean(axis=0),
                                       atol=1e-4)
        print("OK")
    """)
    assert "OK" in out


def test_overlap_training_consensus_within_2x_of_masked():
    """Acceptance: at equal iterations on the tiny preset the overlap
    mode's consensus distance stays within 2x of masked, and the loss
    still falls."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import get_smoke_config
        from repro.core import paper_figure1_graph, plan_matcha
        from repro.data.pipeline import DecentralizedBatches
        from repro.dist import decen_train as dt
        from repro.dist import sharding as shd
        from repro.launch.mesh import make_test_mesh
        from repro.models.transformer import Model
        from repro.optim.optimizers import sgd

        g = paper_figure1_graph()
        cfg = get_smoke_config("internlm2_1_8b")
        model = Model(cfg)
        mesh = make_test_mesh(nodes=8, model=1)
        spec = dt.make_spec(mesh, cfg, multi_pod=False)
        plan = plan_matcha(g, 0.5, budget_steps=400)
        sched = plan.schedule(60, seed=1)

        results = {}
        for mode in ("masked", "overlap"):
            opt = sgd(0.3, momentum=0.9)
            params = dt.init_stacked_params(model, spec, seed=0)
            params = jax.tree.map(
                lambda a: a + 0.01 * jax.random.normal(
                    jax.random.key(7), a.shape, a.dtype)
                if a.dtype == jnp.float32 else a, params)
            opt_state = dt.init_stacked_opt_state(opt, model, spec)
            pspecs = dt.stacked_param_shardings(model, spec)
            data = DecentralizedBatches(cfg, 8, 4, 64, seed=0)
            it = iter(data)
            with jax.set_mesh(mesh):
                params = jax.device_put(params, shd.named_shardings(pspecs, mesh))
                kw = {}
                gstate = None
                if mode == "overlap":
                    bplan = dt.param_bucket_plan(model)
                    gstate = dt.init_gossip_state(plan, spec, bplan)
                    kw["bucket_plan"] = bplan
                step = dt.make_train_step(model, opt, plan, spec,
                                          gossip_mode=mode, **kw)
                first = None
                for k in range(60):
                    bits = jnp.asarray(sched.activations[k].astype(np.float32))
                    if mode == "overlap":
                        params, opt_state, gstate, losses, _ = step(
                            params, opt_state, gstate, next(it), bits)
                    else:
                        params, opt_state, losses, _ = step(
                            params, opt_state, next(it), bits)
                    if first is None:
                        first = float(jnp.mean(losses))
                if mode == "overlap":
                    params = dt.make_gossip_flush(model, plan, spec, bplan)(
                        params, gstate)
            results[mode] = (first, float(jnp.mean(losses)),
                             float(dt.consensus_distance(params)))
        f_o, l_o, c_o = results["overlap"]
        f_m, l_m, c_m = results["masked"]
        assert l_o < f_o - 0.3, f"overlap loss did not decrease: {f_o} -> {l_o}"
        assert c_o <= 2.0 * c_m, (
            f"overlap consensus {c_o} worse than 2x masked {c_m}")
        print("OK", results)
    """)
    assert "OK" in out


def test_make_spec_rejects_pod_axis_mismatch():
    """A pod-axis mesh with multi_pod=False must raise instead of
    silently gossiping on a quarter of the nodes (and vice versa)."""
    out = run_sub("""
        from repro.configs.registry import get_smoke_config
        from repro.dist import decen_train as dt
        from repro.launch.mesh import make_test_mesh, num_nodes

        cfg = get_smoke_config("internlm2_1_8b")
        mesh_mp = make_test_mesh(nodes=8, model=1, multi_pod=True)
        mesh_sp = make_test_mesh(nodes=8, model=1)

        assert dt.make_spec(mesh_mp, cfg, multi_pod=True).num_nodes == 8
        assert dt.make_spec(mesh_sp, cfg, multi_pod=False).num_nodes == 8
        assert num_nodes(mesh_mp, multi_pod=True) == 8

        for mesh, flag in ((mesh_mp, False), (mesh_sp, True)):
            try:
                dt.make_spec(mesh, cfg, multi_pod=flag)
            except ValueError as e:
                assert "pod" in str(e)
            else:
                raise AssertionError(f"no error for multi_pod={flag}")
        print("OK")
    """)
    assert "OK" in out
