"""The fused Pallas gossip-axpy path must agree with the jnp reference
INSIDE an actual ``mix_matchings`` call (not just in isolation): same
ppermute exchanges, same accumulated target, the only difference being
whether the final x + alpha*(target - x) runs through the Pallas kernel
(interpret mode on CPU) or ``repro.kernels.ref.gossip_axpy_ref``.

Needs a multi-device host, so it runs in a subprocess like
tests/test_dist_multidevice.py.
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


def test_pallas_gossip_path_matches_ref_inside_mix_matchings():
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
        info = NodeAxisInfo(axis_names=("data",), num_nodes=8)
        active = tuple(range(plan.num_matchings))
        x = {"w": jax.random.normal(jax.random.key(0), (8, 33, 7)),
             "b": jax.random.normal(jax.random.key(1), (8, 129))}
        specs = jax.tree.map(lambda _: P("data"), x)
        bits = jnp.ones((plan.num_matchings,), jnp.float32)

        def run(impl):
            def body(xs, bits):
                local = jax.tree.map(lambda a: a[0], xs)
                out_s = mix_matchings(local, plan.alpha, plan.permutations,
                                      active, info, impl=impl)
                out_m = mix_matchings_masked(local, plan.alpha,
                                             plan.permutations, bits, info,
                                             impl=impl)
                ex = lambda t: jax.tree.map(lambda a: a[None], t)
                return ex(out_s), ex(out_m)
            f = jax.shard_map(body, mesh=mesh, in_specs=(specs, P()),
                              out_specs=(specs, specs), axis_names={"data"},
                              check_vma=False)
            return jax.jit(f)(x, bits)

        with jax.set_mesh(mesh):
            # "interpret" forces the fused kernel path on CPU ("pallas"
            # now means the compiled kernel, which only lowers on TPU)
            pallas_s, pallas_m = run("interpret")
            ref_s, ref_m = run("xla")            # gossip_axpy_ref

        for a, b in zip(jax.tree.leaves(pallas_s), jax.tree.leaves(ref_s)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-7, rtol=0)
        for a, b in zip(jax.tree.leaves(pallas_m), jax.tree.leaves(ref_m)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-7, rtol=0)

        # and both match the dense mixing-matrix oracle
        L = sum(m.laplacian() for m in plan.matchings)
        W = np.eye(8) - plan.alpha * L
        want = mix_dense(x, jnp.asarray(W))
        for a, b in zip(jax.tree.leaves(pallas_s), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
        print("OK")
    """)
    assert "OK" in out


def test_tensor_parallel_leaves_update_shard_by_shard():
    """On a (4 nodes x 2 model) mesh the kernel region takes each leaf's
    model-axis spec: a tensor-parallel leaf is updated on its local
    shards (no all-gather in the program), and the exchange still equals
    the dense mixing oracle. Without the specs the leaves are gathered,
    which shows the check can see a gather."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import plan_matcha, ring_graph
        from repro.dist.gossip import (
            NodeAxisInfo, mix_dense, mix_matchings_masked,
        )
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh(nodes=4, model=2)
        plan = plan_matcha(ring_graph(4), 0.5, seed=0)
        info = NodeAxisInfo(axis_names=("data",), num_nodes=4)
        x = {"w": jax.random.normal(jax.random.key(0), (4, 32, 6)),
             "b": jax.random.normal(jax.random.key(1), (4, 10))}
        stacked = {"w": P("data", "model"), "b": P("data")}
        local = {"w": P("model"), "b": P()}
        bits = jnp.ones((plan.num_matchings,), jnp.float32)

        def run(leaf_specs):
            def body(xs, bits):
                mine = jax.tree.map(lambda a: a[0], xs)
                out = mix_matchings_masked(
                    mine, plan.alpha, plan.permutations, bits, info,
                    impl="interpret", leaf_specs=leaf_specs)
                return jax.tree.map(lambda a: a[None], out)
            f = jax.jit(jax.shard_map(
                body, mesh=mesh, in_specs=(P("data"), P()),
                out_specs=P("data"), axis_names={"data"}, check_vma=False))
            xs = jax.device_put(x, {k: NamedSharding(mesh, s)
                                    for k, s in stacked.items()})
            text = f.lower(xs, bits).compile().as_text()
            return f(xs, bits), text

        with jax.set_mesh(mesh):
            got, text = run(local)
            _, gathered = run(None)
        assert "all-gather" not in text
        assert "all-gather" in gathered

        L = sum(m.laplacian() for m in plan.matchings)
        W = np.eye(4) - plan.alpha * L
        want = mix_dense(x, jnp.asarray(W, jnp.float32))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6, rtol=0)
        print("OK")
    """)
    assert "OK" in out
