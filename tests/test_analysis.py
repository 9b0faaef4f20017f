"""Static-analysis suite (``repro.analysis``).

Three layers, mirroring the analyzer's threat model:

* unit: plan-time permutation validation and the checkers, fed
  synthetic adversarial inputs (non-involution ppermutes, oversized
  gathers, wrong axes) — each must produce its *named* violation.
* traced: adversarial jaxprs (a ring-shift ppermute, an f64 leak, an
  unwhitelisted fp32 upcast attributed to ``dist/gossip.py``) walked by
  the real traversal/collect pipeline.
* mutation: the CI gate itself.  ``python -m repro.analysis.check
  --strict`` must exit non-zero when a bad permutation or an oversized
  all-gather is injected into the dist layer — proof the gate would
  catch the regression it exists for.

Multi-device bodies run in subprocesses (XLA host device count must be
set before jax initializes), like tests/test_stream_fsdp.py.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(body)],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=REPO,
    )
    assert res.returncode == 0, f"stderr:\n{res.stderr[-4000:]}"
    return res.stdout


# ---------------------------------------------------------------------------
# Plan-time validation (core/matching.py)
# ---------------------------------------------------------------------------
def test_validate_permutations_accepts_involutions():
    from repro.core.matching import validate_permutations

    ok = np.array([[1, 0, 3, 2], [0, 1, 2, 3], [2, 1, 0, 3]])
    out = validate_permutations(ok, 4)
    assert out.shape == (3, 4)


def test_validate_permutations_names_the_bad_matching():
    from repro.core.matching import validate_permutations

    with pytest.raises(ValueError, match="matching 1.*out of range"):
        validate_permutations(np.array([[1, 0, 2, 3], [0, 1, 2, 4]]), 4)
    with pytest.raises(ValueError, match="matching 0.*degree <= 1"):
        validate_permutations(np.array([[1, 0, 0, 3]]), 4)
    with pytest.raises(ValueError, match="matching 0.*not an involution"):
        # ring shift: a valid permutation, but partners don't swap
        validate_permutations(np.array([[1, 2, 3, 0]]), 4)
    with pytest.raises(ValueError, match="must be integer"):
        validate_permutations(np.array([[1.0, 0.0]]), 2)


def test_plan_matcha_rows_validate_and_export_pairs():
    from repro.core import plan_matcha, ring_graph
    from repro.core.matching import validate_permutations

    plan = plan_matcha(ring_graph(4), 0.5, budget_steps=50)
    validate_permutations(plan.permutations, 4)
    pairs = plan.ppermute_pairs()
    assert len(pairs) == plan.num_matchings
    for row in pairs:
        assert {s for s, _ in row} == {0, 1, 2, 3}


# ---------------------------------------------------------------------------
# Checkers on synthetic adversarial records (no devices needed)
# ---------------------------------------------------------------------------
def _rec(**kw):
    from repro.analysis.collectives import CollectiveRecord

    base = dict(
        kind="ppermute", axes=("data",), dtype="float32", shape=(8,),
        bytes=32, scan_trips=1, in_manual=True, perm=None, path=(),
        source=(),
    )
    base.update(kw)
    return CollectiveRecord(**base)


def _names(viols):
    return [v.name for v in viols]


def test_check_ppermutes_adversarial_records():
    from repro.analysis import checks

    planned = (((0, 1), (1, 0), (2, 3), (3, 2)),)
    good = _rec(perm=((0, 1), (1, 0), (2, 3), (3, 2)))
    assert checks.check_ppermutes(
        [good], num_nodes=4, node_axes=("data",),
        planned_pairs=planned, expect_all_planned=True) == []

    shift = _rec(perm=((0, 1), (1, 2), (2, 3), (3, 0)))
    names = _names(checks.check_ppermutes(
        [shift], num_nodes=4, node_axes=("data",), planned_pairs=planned))
    assert "ppermute-not-involution" in names
    assert "ppermute-unplanned" in names

    oob = _rec(perm=((0, 5), (1, 1), (2, 2), (3, 3)))
    assert "ppermute-out-of-range" in _names(checks.check_ppermutes(
        [oob], num_nodes=4, node_axes=("data",)))

    dup = _rec(perm=((0, 1), (2, 1), (1, 0), (3, 3)))
    assert "ppermute-duplicate-dest" in _names(checks.check_ppermutes(
        [dup], num_nodes=4, node_axes=("data",)))

    on_shard = _rec(perm=((0, 1), (1, 0), (2, 3), (3, 2)), axes=("shard",))
    assert "ppermute-bad-axes" in _names(checks.check_ppermutes(
        [on_shard], num_nodes=4, node_axes=("data",)))

    # masked modes must exchange every planned matching
    assert "matching-not-exchanged" in _names(checks.check_ppermutes(
        [], num_nodes=4, node_axes=("data",),
        planned_pairs=planned, expect_all_planned=True))


def test_check_collective_axes_contract():
    from repro.analysis import checks

    ok = _rec(kind="all_gather", axes=("shard",))
    assert checks.check_collective_axes([ok]) == []
    bad = _rec(kind="all_gather", axes=("data",))
    assert _names(checks.check_collective_axes([bad])) == [
        "collective-bad-axes"
    ]
    bad_psum = _rec(kind="psum", axes=("data",))
    assert _names(checks.check_collective_axes([bad_psum])) == [
        "collective-bad-axes"
    ]
    from repro.dist import bucketing

    leaked = _rec(kind="psum", axes=("shard",),
                  source=(bucketing.__file__, "ravel", 1))
    assert "collective-in-bucketing" in _names(
        checks.check_collective_axes([leaked]))


def test_check_bytes_fsdp_oversized_gather():
    from repro.analysis import checks

    row = {
        "per_matching_comm_bytes": 1000,
        "peak_transient_bytes_monolithic": 4000,
        "peak_transient_bytes_streamed": 2000,
        "peak_transient_bytes_scan_streamed": 2000,
    }
    good = [
        _rec(perm=((0, 1), (1, 0)), bytes=1000),
        _rec(kind="all_gather", axes=("shard",), perm=None, bytes=2000),
    ]
    assert checks.check_bytes_fsdp(
        good, row, layout_kind="streamed", gossip=True) == []
    # a gather breaching the streamed layout's byte budget
    oversized = [
        _rec(perm=((0, 1), (1, 0)), bytes=1000),
        _rec(kind="all_gather", axes=("shard",), perm=None, bytes=4000),
    ]
    assert "bytes-mismatch" in _names(checks.check_bytes_fsdp(
        oversized, row, layout_kind="streamed", gossip=True))
    # gossip step that traced no exchanges at all
    assert "bytes-mismatch" in _names(checks.check_bytes_fsdp(
        [_rec(kind="all_gather", axes=("shard",), perm=None, bytes=2000)],
        row, layout_kind="streamed", gossip=True))


def test_memory_ladder_bounds_per_layout():
    """The ladder checker on real layouts: a max-fp just at the bound is
    clean, one element above it is ``ladder-bound-exceeded``, and a
    scan-stack-sized intermediate is ``scan-residual-materialized``."""
    out = run_sub("""
        import jax
        from repro.analysis import checks
        from repro.configs.base import ModelConfig
        from repro.dist import decen_train as dt
        from repro.dist import fsdp
        from repro.launch.mesh import make_test_mesh
        from repro.models.transformer import Model
        cfg = ModelConfig(
            name="micro-deep-moe", family="moe", num_layers=8, d_model=64,
            num_heads=4, num_kv_heads=2, head_dim=16, d_ff=96,
            moe_num_experts=4, moe_top_k=2, moe_d_ff=96, moe_every=1,
            vocab_size=256, ffn_activation="silu", gated_ffn=True,
            pos_embed="rope", tie_embeddings=True, source="test",
            compute_dtype="float32", scan_layers=True,
        )
        model = Model(cfg)
        mesh = make_test_mesh(nodes=4, model=1, shard=2)
        spec = dt.make_spec(mesh, cfg)
        layout = fsdp.make_stream_layout(model, spec)
        bound = checks.ladder_bound(layout)
        assert checks.check_memory_ladder(bound, layout) == []
        names = [v.name for v in checks.check_memory_ladder(bound + 1, layout)]
        assert "ladder-bound-exceeded" in names, names
        stack = max(layout.plan.bucket_sizes)
        names = [v.name for v in checks.check_memory_ladder(stack, layout)]
        assert "scan-residual-materialized" in names, names
        mono = fsdp.make_layout(model, spec)
        names = [v.name for v in checks.check_memory_ladder(
            mono.plan.total_elements - 1, mono)]
        assert names == ["monolithic-not-materialized"], names
        assert checks.check_memory_ladder(
            mono.plan.total_elements, mono) == []
        print("OK")
    """)
    assert "OK" in out


# ---------------------------------------------------------------------------
# Adversarial traced jaxprs through the real traversal/collect pipeline
# ---------------------------------------------------------------------------
def test_traced_ring_shift_ppermute_is_flagged():
    out = run_sub("""
        import jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.analysis import checks
        from repro.analysis.collectives import collect

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("data",))

        @partial(jax.shard_map, mesh=mesh, in_specs=P("data"),
                 out_specs=P("data"),
                 check_vma=False)
        def bad_gossip(x):
            # ring shift: a legal ppermute, an illegal matching
            return jax.lax.ppermute(
                x, "data", [(i, (i + 1) % 4) for i in range(4)])

        records = collect(bad_gossip, jnp.zeros((4, 8), jnp.float32))
        assert len(records) == 1 and records[0].kind == "ppermute"
        names = [v.name for v in checks.check_ppermutes(
            records, num_nodes=4, node_axes=("data",))]
        assert "ppermute-not-involution" in names, names
        print("OK")
    """)
    assert "OK" in out


def test_traced_f64_leak_is_flagged():
    out = run_sub("""
        import jax
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp
        from repro.analysis import checks

        def leaky(x):
            return jnp.sum(x.astype(jnp.float64))

        names = [v.name for v in checks.check_dtypes(
            leaky, jnp.zeros((8,), jnp.float32))]
        assert "f64-leak" in names, names
        # and a clean fp32 program stays clean under x64 mode
        assert checks.check_dtypes(
            lambda x: jnp.sum(x), jnp.zeros((8,), jnp.float32)) == []
        print("OK")
    """)
    assert "OK" in out


def test_unwhitelisted_dist_layer_upcast_is_flagged():
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro.analysis import checks
        from repro.dist import gossip

        # compile a rogue upcast attributed to dist/gossip.py, like a
        # helper someone added without declaring it in FP32_UPCAST_SITES
        ns = {"jnp": jnp}
        exec(compile("def rogue(x):\\n    return x.astype(jnp.float32)\\n",
                     gossip.__file__, "exec"), ns)
        rogue = ns["rogue"]

        names = [v.name for v in checks.check_dtypes(
            rogue, jnp.zeros((8,), jnp.bfloat16))]
        assert names == ["fp32-upcast-unwhitelisted"], names

        # the declared accumulation sites stay clean: a real masked
        # gossip trace upcasts only inside FP32_UPCAST_SITES
        import numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("data",))
        info = gossip.NodeAxisInfo(("data",), 4)
        perms = np.array([[1, 0, 3, 2]])

        @partial(jax.shard_map, mesh=mesh, in_specs=P("data"),
                 out_specs=P("data"),
                 check_vma=False)
        def step(x):
            return gossip.mix_matchings_masked(
                x, 0.5, perms, jnp.ones((1,), jnp.float32), info)

        assert checks.check_dtypes(
            step, jnp.zeros((4, 8), jnp.bfloat16)) == []
        print("OK")
    """)
    assert "OK" in out


# ---------------------------------------------------------------------------
# Mutation tests: the CI gate must fail on injected regressions
# ---------------------------------------------------------------------------
def _run_gate(mutation: str, cli: str) -> str:
    """Run ``repro.analysis.check --strict`` in-process after applying a
    mutation to the dist/kernel/planner layer; print rc + the violation
    names from every report section (steps, plan, schedule, kernels)."""
    return run_sub("""
        import json, sys
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.analysis import check
        from repro.core import matcha as mc
        from repro.core.budget import BudgetSolution
        from repro.dist import fsdp, gossip
        from repro.kernels import flash_attention as fa
        from repro.kernels import gossip_axpy as ga
""" + mutation + """
        import contextlib, io
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = check.main(""" + cli + """)
        report = json.loads(buf.getvalue())
        viols = [v for s in report["steps"].values()
                 for v in s["violations"]]
        viols += report["plan"]["violations"]
        viols += report["schedule"]["violations"]
        viols += report["artifact"]["violations"]
        viols += [v for c in report["kernels"]["cases"].values()
                  for v in c["violations"]]
        viols += report["kernels"]["interpret_lint"]
        names = sorted({v["name"] for v in viols})
        print("rc:", rc)
        print("violations:", names)
    """)


def test_gate_fails_on_injected_bad_permutation():
    """Mutate ``gossip._pairs`` into a ring shift: every traced exchange
    is now a non-involution, and the strict gate must exit 1."""
    out = _run_gate(
        """
        def _shifted(perm):
            n = len(perm)
            return [(i, (i + 1) % n) for i in range(n)]
        gossip._pairs = _shifted
""",
        '["--shard", "1", "--layouts", "monolithic",'
        ' "--gossip-modes", "masked", "--strict"]',
    )
    assert "rc: 1" in out, out
    assert "ppermute-not-involution" in out, out
    assert "ppermute-unplanned" in out, out


def test_gate_fails_on_injected_oversized_gather():
    """Mutate ``fsdp._materialize_group`` to gather a 16x-tiled shard: the
    streamed step's largest transient breaches both the byte budget and
    the memory ladder, and the strict gate must exit 1."""
    out = _run_gate(
        """
        _orig = fsdp._materialize_group
        def _bloated(layout, gi, shard):
            sub = _orig(layout, gi, shard)
            big = jax.lax.all_gather(
                jnp.tile(shard, 16), "shard", tiled=True)
            leak = jnp.sum(big) * 1e-30
            return jax.tree.map(lambda a: a + leak.astype(a.dtype), sub)
        fsdp._materialize_group = _bloated
""",
        '["--shard", "2", "--layouts", "streamed",'
        ' "--gossip-modes", "none", "--strict"]',
    )
    assert "rc: 1" in out, out
    assert "bytes-mismatch" in out, out
    assert "ladder-bound-exceeded" in out, out


def test_gate_passes_unmutated_subset():
    """Control for the mutation pair: the same gate invocation on the
    unmutated tree exits 0 with zero violations."""
    out = _run_gate(
        "",
        '["--shard", "2", "--layouts", "streamed",'
        ' "--gossip-modes", "none", "--strict"]',
    )
    assert "rc: 0" in out, out
    assert "violations: []" in out, out


# ---------------------------------------------------------------------------
# Mutation tests: the kernel-lint / schedule-verifier gate
# ---------------------------------------------------------------------------
def test_gate_fails_on_shifted_kernel_index_map():
    """Shift flash attention's q index map one block off-grid: the last
    grid step now reads past the array, and the kernel lint must catch
    it (the kernel resolves its index maps from module globals at trace
    time, so the patch reaches the traced pallas_call)."""
    out = _run_gate(
        """
        fa.q_index_map = lambda b, h, iq, ik: (b, h, iq + 1, 0)
""",
        '["--skip-steps", "--strict"]',
    )
    assert "rc: 1" in out, out
    assert "index-map-out-of-bounds" in out, out


def test_gate_fails_on_removed_masked_tail_guard():
    """Drop the kv_len mask from the ragged attention path: the padded
    key positions are no longer guarded in the kernel body and the
    masked-tail check must flag the declared guard as missing."""
    out = _run_gate(
        """
        _orig_fa = fa.flash_attention
        def _unmasked(*a, **kw):
            kw["kv_len"] = 0
            return _orig_fa(*a, **kw)
        fa.flash_attention = _unmasked
""",
        '["--skip-steps", "--strict"]',
    )
    assert "rc: 1" in out, out
    assert "masked-tail-guard-missing" in out, out


def test_gate_fails_on_bf16_accumulator():
    """Demote the gossip-axpy accumulation dtype to bf16: the ragged
    bf16 shard case now runs the consensus update without the fp32
    widening its contract requires, and the strict gate must exit 1."""
    out = _run_gate(
        """
        ga.ACC_DTYPE = jnp.bfloat16
""",
        '["--skip-steps", "--strict"]',
    )
    assert "rc: 1" in out, out
    assert "acc-dtype-not-fp32" in out, out


def test_gate_fails_on_non_contractive_plan():
    """Degenerate the budget optimizer so only matching 0 ever activates
    (disconnected expectation graph -> rho >= 1), and stub out the
    planner's own verify_spectral so the plan actually builds: the
    schedule verifier in analysis.check is the independent backstop and
    must still fail the gate."""
    out = _run_gate(
        """
        mc.verify_spectral = lambda plan, **kw: plan.rho
        _orig_opt = mc.optimize_activation_probabilities
        def _degenerate(matchings, comm_budget, **kw):
            sol = _orig_opt(matchings, comm_budget, **kw)
            p = np.zeros_like(sol.probabilities)
            p[0] = 1.0
            return BudgetSolution(
                probabilities=p, lambda2=sol.lambda2,
                budget=sol.budget, iterations=sol.iterations,
            )
        mc.optimize_activation_probabilities = _degenerate
""",
        '["--skip-steps", "--kernel-sweep", "none", "--strict"]',
    )
    assert "rc: 1" in out, out
    assert "expectation-graph-disconnected" in out, out
    assert "schedule-rho-not-contractive" in out, out


def test_gate_passes_unmutated_kernel_and_schedule():
    """Control for the kernel/schedule mutations: the same --skip-steps
    invocation on the unmutated tree exits 0 with zero violations."""
    out = _run_gate("", '["--skip-steps", "--strict"]')
    assert "rc: 0" in out, out
    assert "violations: []" in out, out
