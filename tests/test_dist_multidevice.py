"""Distributed runtime tests on a multi-device CPU mesh.

XLA's host device count must be set before jax initializes, and the
assignment forbids forcing it globally (smoke tests must see 1 device),
so each test here runs its body in a fresh subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8.
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


def test_gossip_matches_dense_mixing_matrix():
    """shard_map ppermute gossip == x @ W with W = I - alpha sum L_j."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import plan_matcha, paper_figure1_graph
        from repro.dist.gossip import NodeAxisInfo, mix_matchings, mix_matchings_masked, mix_dense
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh(nodes=8, model=1)
        g = paper_figure1_graph()
        plan = plan_matcha(g, 0.5, budget_steps=500)
        info = NodeAxisInfo(axis_names=("data",), num_nodes=8)
        active = (0, 2, 4)
        x = {"w": jax.random.normal(jax.random.key(0), (8, 16, 8)),
             "b": jax.random.normal(jax.random.key(1), (8, 5))}
        specs = jax.tree.map(lambda _: P("data"), x)

        def run_static(xs):
            local = jax.tree.map(lambda a: a[0], xs)
            out = mix_matchings(local, plan.alpha, plan.permutations, active, info)
            return jax.tree.map(lambda a: a[None], out)

        def run_masked(xs, bits):
            local = jax.tree.map(lambda a: a[0], xs)
            out = mix_matchings_masked(local, plan.alpha, plan.permutations, bits, info)
            return jax.tree.map(lambda a: a[None], out)

        with jax.set_mesh(mesh):
            f = jax.shard_map(run_static, mesh=mesh, in_specs=(specs,),
                              out_specs=specs, axis_names={"data"},
                              check_vma=False)
            got = jax.jit(f)(x)
            bits = np.zeros(plan.num_matchings, np.float32); bits[list(active)] = 1
            fm = jax.shard_map(run_masked, mesh=mesh, in_specs=(specs, P()),
                               out_specs=specs, axis_names={"data"},
                               check_vma=False)
            got_m = jax.jit(fm)(x, jnp.asarray(bits))

        L = sum(plan.matchings[j].laplacian() for j in active)
        W = np.eye(8) - plan.alpha * L
        want = mix_dense(x, jnp.asarray(W))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
        for a, b in zip(jax.tree.leaves(got_m), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
        print("OK")
    """)
    assert "OK" in out


def test_decentralized_training_loss_decreases_and_consensus():
    """120 steps on 8 nodes: loss falls; gossip keeps replicas together;
    without gossip ('local') consensus distance blows up."""
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
        sched = plan.schedule(120, seed=1)

        results = {}
        for mode in ("masked", "none"):
            opt = sgd(0.3, momentum=0.9)
            params = dt.init_stacked_params(model, spec, seed=0)
            # per-node perturbation so consensus is non-trivial
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
                step = dt.make_train_step(model, opt, plan, spec, gossip_mode=mode)
                first = None
                for k in range(120):
                    bits = jnp.asarray(sched.activations[k].astype(np.float32))
                    params, opt_state, losses, _ = step(params, opt_state, next(it), bits)
                    if first is None:
                        first = float(jnp.mean(losses))
            results[mode] = (first, float(jnp.mean(losses)),
                             float(dt.consensus_distance(params)))
        first_l, last_l, c = results["masked"]
        assert last_l < first_l - 0.3, (
            f"loss did not decrease: {first_l} -> {last_l}")
        assert c < results["none"][2], "gossip must reduce consensus distance"
        print("OK", results)
    """)
    assert "OK" in out


def test_matcha_cb1_equals_vanilla_training():
    """CB=1.0 MATCHA step == static full-graph gossip (same losses)."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import get_smoke_config
        from repro.core import paper_figure1_graph, plan_matcha, plan_vanilla
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

        losses_by_mode = {}
        for name, plan in (("m1", plan_matcha(g, 1.0)), ("van", plan_vanilla(g))):
            opt = sgd(0.2, momentum=0.9)
            params = dt.init_stacked_params(model, spec, seed=0)
            opt_state = dt.init_stacked_opt_state(opt, model, spec)
            pspecs = dt.stacked_param_shardings(model, spec)
            data = DecentralizedBatches(cfg, 8, 2, 32, seed=0)
            it = iter(data)
            hist = []
            with jax.set_mesh(mesh):
                params = jax.device_put(params, shd.named_shardings(pspecs, mesh))
                active = tuple(range(plan.num_matchings))
                step = dt.make_train_step(model, opt, plan, spec,
                                          gossip_mode="static", active=active)
                bits = jnp.ones((plan.num_matchings,), jnp.float32)
                for k in range(10):
                    params, opt_state, losses, _ = step(params, opt_state, next(it), bits)
                    hist.append(float(jnp.mean(losses)))
            losses_by_mode[name] = hist
        a, b = losses_by_mode["m1"], losses_by_mode["van"]
        np.testing.assert_allclose(a, b, rtol=1e-4)
        print("OK")
    """)
    assert "OK" in out


def test_tensor_parallel_matches_single_device():
    """Same seed, (4 nodes x 2 TP) vs single-device per-node eval: the
    distributed forward must match the unsharded forward."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import get_smoke_config
        from repro.dist import sharding as shd
        from repro.launch.mesh import make_test_mesh
        from repro.models.transformer import Model

        cfg = get_smoke_config("internlm2_1_8b")
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab_size)
        ref, _ = model.forward(params, tokens)

        mesh = make_test_mesh(nodes=2, model=4)
        rules = shd.serve_rules(mesh, cfg)
        pspecs = shd.param_pspecs(model.logical_axes(), rules)
        with jax.set_mesh(mesh):
            params_d = jax.device_put(params, shd.named_shardings(pspecs, mesh))
            def fwd(p, t):
                with shd.use_rules(rules):
                    return model.forward(p, t)[0]
            got = jax.jit(fwd)(params_d, tokens)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            atol=3e-2, rtol=3e-2)
        print("OK")
    """)
    assert "OK" in out


def test_multipod_gossip_over_pod_axis():
    """(2 pods x 4 data) = 8 nodes: ppermute across the pod boundary."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import plan_matcha, ring_graph, matching_decomposition
        from repro.dist.gossip import NodeAxisInfo, mix_matchings, mix_dense
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh(nodes=8, model=1, multi_pod=True)
        g = ring_graph(8)
        plan = plan_matcha(g, 0.6, budget_steps=300)
        info = NodeAxisInfo(axis_names=("pod", "data"), num_nodes=8)
        active = tuple(range(plan.num_matchings))
        x = {"w": jax.random.normal(jax.random.key(0), (8, 12))}
        specs = jax.tree.map(lambda _: P(("pod", "data")), x)

        def run(xs):
            local = jax.tree.map(lambda a: a[0], xs)
            out = mix_matchings(local, plan.alpha, plan.permutations, active, info)
            return jax.tree.map(lambda a: a[None], out)

        with jax.set_mesh(mesh):
            f = jax.shard_map(run, mesh=mesh, in_specs=(specs,),
                              out_specs=specs, axis_names={"pod", "data"},
                              check_vma=False)
            got = jax.jit(f)(x)
        L = sum(plan.matchings[j].laplacian() for j in active)
        W = np.eye(8) - plan.alpha * L
        want = mix_dense(x, jnp.asarray(W))
        np.testing.assert_allclose(np.asarray(got["w"]),
                                   np.asarray(want["w"]), atol=1e-5)
        print("OK")
    """)
    assert "OK" in out
