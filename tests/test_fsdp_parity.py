"""FSDP sharded-replica parity suite (``repro.dist.fsdp``).

The sharded runtime must be an *execution detail*, not a different
algorithm: a shard-1 mesh replays the replicated trajectory exactly
(same arithmetic, different layout), and a 2-shard mesh matches it to
fp32 tolerance (the only difference is the fp rounding of averaging
the S sub-batch gradients) — for both the sequential (masked) and the
overlapped one-step-delayed gossip strategies. Per-device param bytes
must shrink by the shard factor, and gather-on-save checkpoints must be
interchangeable with the replicated format.

Multi-device bodies run in subprocesses (XLA host device count must be
set before jax initializes), like tests/test_gossip_parity.py.
"""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# fp32 compute: the parity comparison is about layout, so the model must
# not inject bf16 rounding noise of its own (indented to splice into the
# 8-space run_sub bodies before dedent)
MICRO_CFG = """\
        cfg = ModelConfig(
            name="micro", family="dense", num_layers=2, d_model=64,
            num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
            ffn_activation="silu", gated_ffn=True, pos_embed="rope",
            tie_embeddings=True, source="test", compute_dtype="float32",
        )
"""


def run_sub(body: str, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(body)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert res.returncode == 0, f"stderr:\n{res.stderr[-4000:]}"
    return res.stdout


def test_shard1_replays_replicated_trajectory_exactly():
    """A size-1 shard axis selects the fsdp runtime but must reproduce
    the replicated masked trajectory (fp32 params): the
    all-gather/reduce-scatter degenerate to identity and every update is
    the same elementwise arithmetic in bucket layout. The first step is
    bit-identical; later steps agree to a few ulps."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import ModelConfig
        from repro.core import plan_matcha, ring_graph
        from repro.data.pipeline import DecentralizedBatches
        from repro.dist import decen_train as dt
        from repro.dist import fsdp
        from repro.dist import sharding as shd
        from repro.launch.mesh import make_test_mesh
        from repro.models.transformer import Model
        from repro.optim.optimizers import sgd
""" + MICRO_CFG + """
        model = Model(cfg)
        plan = plan_matcha(ring_graph(4), 0.5, budget_steps=200)
        K = 5
        sched = plan.schedule(K, seed=1)
        data = DecentralizedBatches(cfg, 4, 4, 32, seed=0)
        it = iter(data)
        batches = [next(it) for _ in range(K)]
        bits_rows = [jnp.asarray(sched.activations[k].astype(np.float32))
                     for k in range(K)]

        mesh_u = make_test_mesh(nodes=4, model=1)
        spec_u = dt.make_spec(mesh_u, cfg)
        opt = sgd(0.2, momentum=0.9)
        params = dt.init_stacked_params(model, spec_u, seed=0)
        opt_state = dt.init_stacked_opt_state(opt, model, spec_u)
        with jax.set_mesh(mesh_u):
            pspecs = dt.stacked_param_shardings(model, spec_u)
            params = jax.device_put(params, shd.named_shardings(pspecs, mesh_u))
            step = dt.make_train_step(model, opt, plan, spec_u,
                                      gossip_mode="masked")
            for k in range(K):
                params, opt_state, lu, _ = step(
                    params, opt_state, batches[k], bits_rows[k])
                if k == 0:
                    p_ref1 = jax.device_get(params)
        p_ref = jax.device_get(params)

        mesh_f = make_test_mesh(nodes=4, model=1, shard=1)
        spec_f = dt.make_spec(mesh_f, cfg)
        assert spec_f.num_shards == 1
        layout = fsdp.make_layout(model, spec_f)
        shards = fsdp.init_fsdp_params(model, layout, seed=0)
        fopt = fsdp.init_fsdp_opt_state(opt, layout)
        with jax.set_mesh(mesh_f):
            step = fsdp.make_fsdp_train_step(
                model, opt, plan, spec_f, layout, gossip_mode="sequential")
            for k in range(K):
                shards, fopt, lf, _ = step(
                    shards, fopt, batches[k], bits_rows[k])
                if k == 0:
                    p_f1 = jax.device_get(fsdp.gather_params(layout, shards))
        p_f = jax.device_get(fsdp.gather_params(layout, shards))

        for a, b in zip(jax.tree.leaves(p_ref1), jax.tree.leaves(p_f1)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # then within a few fp32 ulps, not bitwise. The first difference
        # is at step 2, in the tied embedding table alone (7.5e-9, one
        # ulp at its scale): the one leaf whose gradient sums two terms
        # (the lookup's scatter-add, the head's matmul), which the two
        # programs add in different orders. By step 5 it has spread to
        # 1.2e-7 (one ulp at 1.0, on the norm scales); 1e-6 is 8x that.
        for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_f)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(lu).ravel(), np.asarray(lf)[:, 0], rtol=1e-6)
        print("OK")
    """)
    assert "OK" in out


def test_shard2_parity_sequential_and_overlap():
    """Acceptance: on a 2-shard CPU mesh the fsdp step matches the
    unsharded trajectory to fp32 tolerance for both gossip modes,
    per-device param bytes halve, and the gathered checkpoint
    round-trips through the replicated on-disk format."""
    out = run_sub("""
        import os, tempfile
        import jax, jax.numpy as jnp, numpy as np
        from repro.checkpoint import ckpt as ckpt_lib
        from repro.configs.base import ModelConfig
        from repro.core import plan_matcha, ring_graph
        from repro.data.pipeline import DecentralizedBatches
        from repro.dist import decen_train as dt
        from repro.dist import fsdp
        from repro.dist import sharding as shd
        from repro.launch.mesh import make_test_mesh
        from repro.models.transformer import Model
        from repro.optim.optimizers import sgd
""" + MICRO_CFG + """
        model = Model(cfg)
        plan = plan_matcha(ring_graph(4), 0.5, budget_steps=200)
        K = 5
        sched = plan.schedule(K, seed=1)
        data = DecentralizedBatches(cfg, 4, 4, 32, seed=0)
        it = iter(data)
        batches = [next(it) for _ in range(K)]
        bits_rows = [jnp.asarray(sched.activations[k].astype(np.float32))
                     for k in range(K)]
        opt_of = lambda: sgd(0.2, momentum=0.9)

        # ---- replicated references, both strategies
        mesh_u = make_test_mesh(nodes=4, model=1)
        spec_u = dt.make_spec(mesh_u, cfg)
        refs = {}
        with jax.set_mesh(mesh_u):
            pspecs = dt.stacked_param_shardings(model, spec_u)
            for mode in ("masked", "overlap"):
                opt = opt_of()
                params = dt.init_stacked_params(model, spec_u, seed=0)
                params = jax.device_put(
                    params, shd.named_shardings(pspecs, mesh_u))
                opt_state = dt.init_stacked_opt_state(opt, model, spec_u)
                kw, gstate = {}, None
                if mode == "overlap":
                    bplan = dt.param_bucket_plan(model)
                    gstate = dt.init_gossip_state(plan, spec_u, bplan)
                    kw["bucket_plan"] = bplan
                step = dt.make_train_step(model, opt, plan, spec_u,
                                          gossip_mode=mode, **kw)
                for k in range(K):
                    if mode == "overlap":
                        params, opt_state, gstate, _, _ = step(
                            params, opt_state, gstate, batches[k],
                            bits_rows[k])
                    else:
                        params, opt_state, _, _ = step(
                            params, opt_state, batches[k], bits_rows[k])
                if mode == "overlap":
                    params = dt.make_gossip_flush(model, plan, spec_u, bplan)(
                        params, gstate)
                refs[mode] = jax.device_get(params)

        # ---- fsdp, 2 shards
        mesh_f = make_test_mesh(nodes=4, model=1, shard=2)
        spec_f = dt.make_spec(mesh_f, cfg)
        assert spec_f.num_shards == 2
        layout = fsdp.make_layout(model, spec_f)
        final = {}
        with jax.set_mesh(mesh_f):
            for mode, ref_mode in (("sequential", "masked"),
                                   ("overlap", "overlap")):
                opt = opt_of()
                shards = fsdp.init_fsdp_params(model, layout, seed=0)
                shards = jax.device_put(shards, shd.named_shardings(
                    fsdp.fsdp_param_pspecs(spec_f, layout), mesh_f))
                fopt = fsdp.init_fsdp_opt_state(opt, layout)
                # per-device state is 1/2 of the (padded) replica
                per_dev = sum(s.shape[2] for s in shards)
                assert per_dev * 2 == layout.plan.total_elements, per_dev
                gstate = None
                if mode == "overlap":
                    gstate = fsdp.init_fsdp_gossip_state(layout)
                step = fsdp.make_fsdp_train_step(
                    model, opt, plan, spec_f, layout, gossip_mode=mode)
                for k in range(K):
                    if mode == "overlap":
                        shards, fopt, gstate, _, _ = step(
                            shards, fopt, gstate, batches[k], bits_rows[k])
                    else:
                        shards, fopt, _, _ = step(
                            shards, fopt, batches[k], bits_rows[k])
                if mode == "overlap":
                    shards = fsdp.make_fsdp_gossip_flush(
                        plan, spec_f, layout)(shards, gstate)
                got = jax.device_get(fsdp.gather_params(layout, shards))
                for a, b in zip(jax.tree.leaves(refs[ref_mode]),
                                jax.tree.leaves(got)):
                    np.testing.assert_allclose(
                        np.asarray(a, np.float32), np.asarray(b, np.float32),
                        atol=5e-5, rtol=5e-5, err_msg=mode)
                final[mode] = (shards, fopt)

        # ---- gather-on-save checkpoint: replicated format, re-scatters
        shards, fopt = final["sequential"]
        d = tempfile.mkdtemp()
        ckpt_lib.save_run(
            d, fsdp.gather_params(layout, shards),
            fsdp.gather_opt_state(layout, fopt), step=K, extra={"shard": 2})
        r_params, r_opt, step_no = ckpt_lib.restore_run(d)
        assert step_no == K
        import json
        assert json.load(open(os.path.join(d, "ckpt.json")))["shard"] == 2
        re_shards = fsdp.scatter_params(layout, r_params)
        for a, b in zip(shards, re_shards):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        re_opt = fsdp.scatter_opt_state(layout, opt_of(), r_opt)
        for a, b in zip(jax.tree.leaves(fopt), jax.tree.leaves(re_opt)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("OK")
    """)
    assert "OK" in out


def test_consensus_distance_sharded_matches_replicated():
    """The logging-path consensus on (nodes, S, slice) shards must equal
    the replicated consensus on the gathered tree (single device — pure
    layout algebra, padding contributes zero)."""
    import jax
    import numpy as np

    from repro.dist import bucketing
    from repro.dist.decen_train import consensus_distance
    from repro.dist.fsdp import consensus_distance_sharded

    tree = {
        "w": jax.random.normal(jax.random.key(0), (4, 5, 3)),
        "b": jax.random.normal(jax.random.key(1), (4, 7)),
    }
    local_abs = jax.eval_shape(lambda t: jax.tree.map(lambda a: a[0], t), tree)
    plan = bucketing.plan_buckets(local_abs, pad_to=2)
    buckets = bucketing.ravel_stacked(plan, tree)
    shards = tuple(b.reshape(b.shape[0], 2, -1) for b in buckets)
    np.testing.assert_allclose(
        float(consensus_distance_sharded(shards)),
        float(consensus_distance(tree)),
        rtol=1e-6,
    )


def test_replicated_builders_reject_shard_mesh():
    """make_train_step on a shard-axis mesh must raise (a replicated
    step would silently keep O(model) per device) and make_layout must
    agree with the mesh's shard factor."""
    out = run_sub("""
        import jax
        from repro.configs.registry import get_smoke_config
        from repro.dist import decen_train as dt
        from repro.dist import fsdp
        from repro.launch.mesh import make_test_mesh, num_shards
        from repro.models.transformer import Model
        from repro.optim.optimizers import sgd
        from repro.core import plan_matcha, ring_graph

        cfg = get_smoke_config("internlm2_1_8b")
        model = Model(cfg)
        mesh = make_test_mesh(nodes=4, model=1, shard=2)
        assert num_shards(mesh) == 2
        assert num_shards(make_test_mesh(nodes=4, model=1)) == 1
        spec = dt.make_spec(mesh, cfg)
        plan = plan_matcha(ring_graph(4), 0.5, budget_steps=100)
        opt = sgd(0.1)
        try:
            dt.make_train_step(model, opt, plan, spec)
        except ValueError as e:
            assert "fsdp" in str(e)
        else:
            raise AssertionError("make_train_step accepted a shard mesh")
        # layout/spec shard-factor mismatch is caught too
        spec1 = dt.make_spec(make_test_mesh(nodes=4, model=1, shard=1), cfg)
        layout1 = fsdp.make_layout(model, spec1)
        try:
            fsdp.make_fsdp_train_step(model, opt, plan, spec, layout1)
        except ValueError as e:
            assert "shard factor" in str(e)
        else:
            raise AssertionError("layout/spec mismatch accepted")
        print("OK")
    """)
    assert "OK" in out
