"""Streaming (layer-grouped) FSDP suite (``repro.dist.fsdp``).

The streaming layout must be an *execution detail* of the same
algorithm: the streamed step applies the identical arithmetic to the
identical bucket values, so at shard=1 it matches the monolithic
trajectory to ULP-level fp32 tolerance (bit-identical is not attainable
between two different XLA modules on CPU — fusion reassociates a few
reductions, observed <= 3 ULP per step even in the fwd loss) and at
shard=2 to the standard fp32 tolerance of the existing fsdp parity
suite, for both the sequential and overlapped gossip strategies. Peak transient memory must actually drop: no fp
intermediate in the streamed step's jaxpr may exceed
``max(group_sizes) + shard_slice`` elements per device, while the
monolithic step materializes the full gathered replica. Checkpoints
are gather-on-save, so the on-disk format is identical across layouts
and a run saved from one restores into the other.

Multi-device bodies run in subprocesses (XLA host device count must be
set before jax initializes), like tests/test_fsdp_parity.py.
"""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# fp32 compute: parity is about layout, not dtype rounding (indented to
# splice into the 8-space run_sub bodies before dedent)
MICRO_CFG = """\
        cfg = ModelConfig(
            name="micro", family="dense", num_layers=2, d_model=64,
            num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
            ffn_activation="silu", gated_ffn=True, pos_embed="rope",
            tie_embeddings=True, source="test", compute_dtype="float32",
        )
"""

# dbrx-shaped shrunk deep registry override: 8 identical MoE blocks ->
# one scanned Segment(count=8) at SCAN_THRESHOLD, exercising the
# scan-streamed (per-iteration row gather) path including aux-loss grads
DEEP_CFG = """\
        cfg = ModelConfig(
            name="micro-deep-moe", family="moe", num_layers=8, d_model=64,
            num_heads=4, num_kv_heads=2, head_dim=16, d_ff=96,
            moe_num_experts=4, moe_top_k=2, moe_d_ff=96, moe_every=1,
            vocab_size=256, ffn_activation="silu", gated_ffn=True,
            pos_embed="rope", tie_embeddings=True, source="test",
            compute_dtype="float32", scan_layers=True,
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


def test_param_groups_cover_tree_in_execution_order():
    """Every top-level param key belongs to exactly one layer group,
    unrolled segments get one group per block (path-prefix + layer
    index), and the grouped ravel/unravel round-trips the tree."""
    import jax
    import numpy as np

    from repro.configs.registry import get_smoke_config
    from repro.dist import decen_train as dt
    from repro.dist import fsdp
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import Model

    cfg = get_smoke_config("internlm2_1_8b")
    model = Model(cfg)
    specs = model.param_group_specs()
    names = [g.name for g in specs]
    assert names[0] == "embed" and names[-1] == "head"
    # 2 smoke layers, unrolled -> one group per block
    assert "blocks_0.0" in names and "blocks_0.1" in names
    params = model.init(jax.random.key(0))
    covered = [k for g in specs for k in g.keys]
    # block groups share their segment key once per layer; dedup
    assert set(covered) == set(params.keys())
    per_layer = [g for g in specs if g.layer is not None]
    assert {g.layer for g in per_layer if g.keys == ("blocks_0",)} == {0, 1}

    mesh = make_mesh((1, 1, 1), ("data", "shard", "model"))
    spec = dt.make_spec(mesh, cfg)
    layout = fsdp.make_stream_layout(model, spec)
    assert layout.plan.names == tuple(names)
    back = layout.unravel_cast(layout.ravel(params))
    got = {str(p): np.asarray(v)
           for p, v in jax.tree_util.tree_leaves_with_path(back)}
    for p, v in jax.tree_util.tree_leaves_with_path(params):
        np.testing.assert_array_equal(got[str(p)], np.asarray(v))


def test_stream_cross_layout_checkpoint_restore():
    """Gather-on-save invariant: a checkpoint written from the streaming
    layout restores into the monolithic layout (and vice versa) because
    the on-disk format is the gathered stacked tree either way."""
    import tempfile

    import jax
    import numpy as np

    from repro.checkpoint import ckpt as ckpt_lib
    from repro.configs.registry import get_smoke_config
    from repro.dist import decen_train as dt
    from repro.dist import fsdp
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import Model
    from repro.optim.optimizers import sgd

    cfg = get_smoke_config("internlm2_1_8b")
    model = Model(cfg)
    mesh = make_mesh((1, 1, 1), ("data", "shard", "model"))
    spec = dt.make_spec(mesh, cfg)
    opt = sgd(0.1, momentum=0.9)
    s_layout = fsdp.make_stream_layout(model, spec)
    m_layout = fsdp.make_layout(model, spec)

    shards = fsdp.init_fsdp_params(model, s_layout, seed=3)
    opt_state = fsdp.init_fsdp_opt_state(opt, s_layout)
    d = tempfile.mkdtemp()
    ckpt_lib.save_run(
        d, fsdp.gather_params(s_layout, shards),
        fsdp.gather_opt_state(s_layout, opt_state), step=7,
        extra={"shard": 1, "stream_layers": True},
    )
    r_params, r_opt, step = ckpt_lib.restore_run(d)
    assert step == 7

    # restore into the monolithic layout: same replicas after gather
    m_shards = fsdp.scatter_params(m_layout, r_params)
    m_opt = fsdp.scatter_opt_state(m_layout, opt, r_opt)
    for (pa, a), (pb, b) in zip(
        jax.tree_util.tree_leaves_with_path(
            fsdp.gather_params(m_layout, m_shards)),
        jax.tree_util.tree_leaves_with_path(
            fsdp.gather_params(s_layout, shards)),
    ):
        assert str(pa) == str(pb)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(
        jax.tree.leaves(fsdp.gather_opt_state(m_layout, m_opt)),
        jax.tree.leaves(fsdp.gather_opt_state(s_layout, opt_state)),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # and back into the streaming layout (restore path round-trip)
    s_shards = fsdp.scatter_params(s_layout, r_params)
    for a, b in zip(s_shards, shards):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stream_shard1_matches_monolithic():
    """shard=1: the streamed step is the same arithmetic as the
    monolithic gather — first-step losses agree to fp32 ULPs (computed
    on identical params) and trajectories stay within a few ULPs over K
    steps. The residual difference is XLA module-level: the streamed
    step re-gathers per group under remat, so CPU fusion reassociates
    some reductions (<= 3 ULP observed)."""
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
        bits = [jnp.asarray(sched.activations[k].astype(np.float32))
                for k in range(K)]

        mesh = make_test_mesh(nodes=4, model=1, shard=1)
        spec = dt.make_spec(mesh, cfg)
        res, first_loss = {}, {}
        with jax.set_mesh(mesh):
            for name, layout in (("mono", fsdp.make_layout(model, spec)),
                                 ("stream", fsdp.make_stream_layout(model, spec))):
                opt = sgd(0.2, momentum=0.9)
                ps = fsdp.init_fsdp_params(model, layout, seed=0)
                st = fsdp.init_fsdp_opt_state(opt, layout)
                step = fsdp.make_fsdp_train_step(
                    model, opt, plan, spec, layout, gossip_mode="sequential")
                for k in range(K):
                    ps, st, loss, _ = step(ps, st, batches[k], bits[k])
                    if k == 0:
                        first_loss[name] = np.asarray(loss)
                res[name] = jax.device_get(fsdp.gather_params(layout, ps))
        # identical params -> the streamed fwd is the same arithmetic
        # (ULP-level: different XLA fusion of the loss reductions)
        np.testing.assert_allclose(
            first_loss["mono"], first_loss["stream"], atol=5e-6, rtol=1e-6)
        for a, b in zip(jax.tree.leaves(res["mono"]),
                        jax.tree.leaves(res["stream"])):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-6, rtol=2e-6)
        print("OK")
    """)
    assert "OK" in out


def test_stream_shard2_parity_sequential_and_overlap():
    """Acceptance: on a 2-shard mesh the streamed step matches the
    monolithic trajectory to fp32 tolerance for both gossip strategies,
    per-device resident bytes still halve, and a checkpoint saved from
    the streamed run re-scatters into the monolithic layout."""
    out = run_sub("""
        import tempfile
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
        K = 4
        sched = plan.schedule(K, seed=1)
        data = DecentralizedBatches(cfg, 4, 4, 32, seed=0)
        it = iter(data)
        batches = [next(it) for _ in range(K)]
        bits = [jnp.asarray(sched.activations[k].astype(np.float32))
                for k in range(K)]

        mesh = make_test_mesh(nodes=4, model=1, shard=2)
        spec = dt.make_spec(mesh, cfg)
        s_layout = fsdp.make_stream_layout(model, spec)
        m_layout = fsdp.make_layout(model, spec)
        # streamed resident state is 1/2 of the (padded) replica too
        assert s_layout.per_device_elements * 2 == s_layout.plan.total_elements
        res = {}
        saved_opt = None
        with jax.set_mesh(mesh):
            for mode in ("sequential", "overlap"):
                for name, layout in (("mono", m_layout), ("stream", s_layout)):
                    opt = sgd(0.2, momentum=0.9)
                    ps = fsdp.init_fsdp_params(model, layout, seed=0)
                    ps = jax.device_put(ps, shd.named_shardings(
                        fsdp.fsdp_param_pspecs(spec, layout), mesh))
                    st = fsdp.init_fsdp_opt_state(opt, layout)
                    gstate = (fsdp.init_fsdp_gossip_state(layout)
                              if mode == "overlap" else None)
                    step = fsdp.make_fsdp_train_step(
                        model, opt, plan, spec, layout, gossip_mode=mode)
                    for k in range(K):
                        if mode == "overlap":
                            ps, st, gstate, loss, _ = step(
                                ps, st, gstate, batches[k], bits[k])
                        else:
                            ps, st, loss, _ = step(ps, st, batches[k], bits[k])
                    if mode == "overlap":
                        ps = fsdp.make_fsdp_gossip_flush(
                            plan, spec, layout)(ps, gstate)
                    res[(mode, name)] = jax.device_get(
                        fsdp.gather_params(layout, ps))
                    if (mode, name) == ("sequential", "stream"):
                        saved_opt = jax.device_get(
                            fsdp.gather_opt_state(layout, st))
        for mode in ("sequential", "overlap"):
            for a, b in zip(jax.tree.leaves(res[(mode, "mono")]),
                            jax.tree.leaves(res[(mode, "stream")])):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b),
                    atol=5e-5, rtol=5e-5, err_msg=mode)

        # cross-layout restore at shard=2: streamed ckpt -> monolithic
        d = tempfile.mkdtemp()
        ckpt_lib.save_run(d, res[("sequential", "stream")], saved_opt, step=K,
                          extra={"shard": 2, "stream_layers": True})
        r_params, _, _ = ckpt_lib.restore_run(d)
        m_shards = fsdp.scatter_params(m_layout, r_params)
        got = fsdp.gather_params(m_layout, m_shards)
        for a, b in zip(jax.tree.leaves(got),
                        jax.tree.leaves(res[("sequential", "stream")])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("OK")
    """)
    assert "OK" in out


def test_stream_memory_shapes():
    """The tentpole's memory claim, checked on traced shapes: no fp
    intermediate inside the streamed step's manual (per-device) region
    exceeds ``max(group_sizes) + shard_slice`` fp32 elements, while the
    monolithic step materializes the full gathered replica
    (``total_elements``) in one intermediate. Pure tracing — nothing
    executes."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import ModelConfig
        from repro.core import plan_matcha, ring_graph
        from repro.dist import decen_train as dt
        from repro.dist import fsdp
        from repro.launch.mesh import make_test_mesh
        from repro.models.transformer import Model
        from repro.optim.optimizers import sgd
""" + MICRO_CFG + """
        model = Model(cfg)
        plan = plan_matcha(ring_graph(4), 0.5, budget_steps=200)
        mesh = make_test_mesh(nodes=4, model=1, shard=2)
        spec = dt.make_spec(mesh, cfg)

        # largest float intermediate inside the manual region — the
        # shared static-analysis walker (repro.analysis.traversal)
        from repro.analysis.traversal import max_fp_intermediate

        opt = sgd(0.2, momentum=0.9)
        bits = jnp.zeros((plan.num_matchings,), jnp.float32)
        batch = {"tokens": jnp.zeros((4, 4, 32), jnp.int32),
                 "labels": jnp.zeros((4, 4, 32), jnp.int32)}
        sizes = {}
        for name, layout in (("mono", fsdp.make_layout(model, spec)),
                             ("stream", fsdp.make_stream_layout(model, spec))):
            ps = jax.eval_shape(
                lambda: fsdp.init_fsdp_params(model, layout, seed=0))
            st = jax.eval_shape(
                lambda: fsdp.init_fsdp_opt_state(opt, layout))
            step = fsdp.make_fsdp_train_step(
                model, opt, plan, spec, layout, gossip_mode="sequential")
            sizes[name] = max_fp_intermediate(step, (ps, st, batch, bits))
            print(name, sizes[name])

        s_layout = fsdp.make_stream_layout(model, spec)
        bound = s_layout.plan.max_group_elements + s_layout.per_device_elements
        total = s_layout.plan.total_elements
        # monolithic really does materialize the whole replica...
        assert sizes["mono"][0] >= total, sizes["mono"]
        # ...the streamed step never exceeds one group + the resident slice
        assert sizes["stream"][0] <= bound, (sizes["stream"], bound)
        # and the drop is real: strictly below the monolithic gather
        assert sizes["stream"][0] < sizes["mono"][0]
        print("OK")
    """)
    assert "OK" in out


def test_scan_stream_parity_shard1_and_2():
    """Scanned-stack parity: on a deep (R=8) scanned MoE stack the
    scan-streamed step (per-iteration row gather, double-buffered
    prefetch, custom-vjp backward re-gather) matches the monolithic
    trajectory at fp32 tolerance — shard 1 and 2, sequential and
    overlap gossip. This pins the whole gradient path: per-row
    ``psum_scatter`` through the all-gather transpose, aux-loss
    cotangents broadcast across scan iterations, and the shard-major
    bucket permutation."""
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
""" + DEEP_CFG + """
        model = Model(cfg)
        specs = model.param_group_specs()
        # the deep stack really is one scanned group of 8 repeats
        assert [g.repeats for g in specs if g.repeats] == [8], specs
        plan = plan_matcha(ring_graph(4), 0.5, budget_steps=200)
        K = 3
        sched = plan.schedule(K, seed=1)
        data = DecentralizedBatches(cfg, 4, 4, 32, seed=0)
        it = iter(data)
        batches = [next(it) for _ in range(K)]
        bits = [jnp.asarray(sched.activations[k].astype(np.float32))
                for k in range(K)]

        for shard_n, tol, modes in (
            (1, 2e-6, ("sequential",)),
            (2, 5e-5, ("sequential", "overlap")),
        ):
            mesh = make_test_mesh(nodes=4, model=1, shard=shard_n)
            spec = dt.make_spec(mesh, cfg)
            s_layout = fsdp.make_stream_layout(model, spec)
            m_layout = fsdp.make_layout(model, spec)
            assert 8 in s_layout.plan.repeats
            res = {}
            with jax.set_mesh(mesh):
                for mode in modes:
                    for name, layout in (("mono", m_layout),
                                         ("stream", s_layout)):
                        opt = sgd(0.2, momentum=0.9)
                        ps = fsdp.init_fsdp_params(model, layout, seed=0)
                        ps = jax.device_put(ps, shd.named_shardings(
                            fsdp.fsdp_param_pspecs(spec, layout), mesh))
                        st = fsdp.init_fsdp_opt_state(opt, layout)
                        gstate = (fsdp.init_fsdp_gossip_state(layout)
                                  if mode == "overlap" else None)
                        step = fsdp.make_fsdp_train_step(
                            model, opt, plan, spec, layout, gossip_mode=mode)
                        for k in range(K):
                            if mode == "overlap":
                                ps, st, gstate, loss, _ = step(
                                    ps, st, gstate, batches[k], bits[k])
                            else:
                                ps, st, loss, _ = step(
                                    ps, st, batches[k], bits[k])
                        if mode == "overlap":
                            ps = fsdp.make_fsdp_gossip_flush(
                                plan, spec, layout)(ps, gstate)
                        res[(mode, name)] = jax.device_get(
                            fsdp.gather_params(layout, ps))
            for mode in modes:
                for a, b in zip(jax.tree.leaves(res[(mode, "mono")]),
                                jax.tree.leaves(res[(mode, "stream")])):
                    np.testing.assert_allclose(
                        np.asarray(a), np.asarray(b), atol=tol, rtol=tol,
                        err_msg=f"shard={shard_n} mode={mode}")
            print(f"shard {shard_n} OK")
        print("OK")
    """, timeout=1200)
    assert "OK" in out


def test_scan_stream_memory_shapes():
    """Acceptance bound for the scanned path: with R=8 repeats, no fp
    intermediate in the scan-streamed step's manual region exceeds
    ``per_layer_elements + shard_slice`` (one gathered row — the
    prefetch buffer is a second, separate row-sized intermediate, never
    a stacked one), while the stack-at-once layout (scan_aware=False)
    materializes the whole ``repeats * per_layer`` group. In particular
    the custom-vjp backward must NOT smuggle an ``(R, per_layer)``
    residual into the jaxpr. Traced with ``gossip_mode="none"``: the
    gossip axpy kernel tiles its resident-shard operands up to
    (256*1024)-element blocks — a resident-sized, layout-independent
    padding that would drown the streamed-path signal this test pins
    (the sequential path is covered by ``test_stream_memory_shapes``).
    Pure tracing — nothing executes."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import ModelConfig
        from repro.core import plan_matcha, ring_graph
        from repro.dist import decen_train as dt
        from repro.dist import fsdp
        from repro.launch.mesh import make_test_mesh
        from repro.models.transformer import Model
        from repro.optim.optimizers import sgd
""" + DEEP_CFG + """
        model = Model(cfg)
        plan = plan_matcha(ring_graph(4), 0.5, budget_steps=200)
        mesh = make_test_mesh(nodes=4, model=1, shard=2)
        spec = dt.make_spec(mesh, cfg)

        # same shared walker as test_stream_memory_shapes
        from repro.analysis.traversal import max_fp_intermediate

        opt = sgd(0.2, momentum=0.9)
        bits = jnp.zeros((plan.num_matchings,), jnp.float32)
        batch = {"tokens": jnp.zeros((4, 4, 32), jnp.int32),
                 "labels": jnp.zeros((4, 4, 32), jnp.int32)}
        sizes = {}
        layouts = {
            "scan": fsdp.make_stream_layout(model, spec),
            "stack": fsdp.make_stream_layout(model, spec, scan_aware=False),
        }
        for name, layout in layouts.items():
            ps = jax.eval_shape(
                lambda: fsdp.init_fsdp_params(model, layout, seed=0))
            st = jax.eval_shape(
                lambda: fsdp.init_fsdp_opt_state(opt, layout))
            step = fsdp.make_fsdp_train_step(
                model, opt, plan, spec, layout, gossip_mode="none")
            sizes[name] = max_fp_intermediate(step, (ps, st, batch, bits))
            print(name, sizes[name])

        s_layout = layouts["scan"]
        assert 8 in s_layout.plan.repeats
        per_layer = s_layout.plan.max_group_elements
        stack = max(s_layout.plan.bucket_sizes)
        assert stack >= 8 * per_layer * 0.9  # the scan group dominates
        bound = per_layer + s_layout.per_device_elements
        # scan-streamed: one row + resident slice, R-independent...
        assert sizes["scan"][0] <= bound, (sizes["scan"], bound)
        # ...and strictly below one layer stack (so the (R, per_layer)
        # residual autodiff would create cannot be present)
        assert sizes["scan"][0] < stack, (sizes["scan"], stack)
        # the stack-at-once layout really gathers the whole group
        assert sizes["stack"][0] >= stack, (sizes["stack"], stack)
        print("OK")
    """)
    assert "OK" in out
