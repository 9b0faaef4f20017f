"""Checkpoint round-trip of the stacked decentralized state.

Guards the ``--resume`` path in ``repro.launch.train``: params +
optimizer state produced by ``repro.dist.decen_train`` must survive
``repro.checkpoint.ckpt.save_run``/``restore_run`` with exact tree
structure, dtypes, and values (both monolithic and per-node layouts).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.configs.registry import get_smoke_config
from repro.dist import decen_train as dt
from repro.launch.mesh import make_mesh
from repro.models.transformer import Model
from repro.optim.optimizers import sgd


def _assert_tree_equal(a, b):
    la, sa = jax.tree.flatten(a)
    lb, sb = jax.tree.flatten(b)
    assert sa == sb, f"tree structure changed: {sa} vs {sb}"
    for x, y in zip(la, lb):
        assert jnp.asarray(x).dtype == jnp.asarray(y).dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_per_node_restore_order_at_128_nodes(tmp_path):
    """Lexicographic file ordering breaks at >= 100 nodes (node_100
    sorts before node_99): the restore must order numerically, so each
    node gets back exactly its own replica."""
    n = 128
    params = {
        "w": jnp.arange(n, dtype=jnp.float32)[:, None] * jnp.ones((1, 3)),
        "b": 1000.0 + jnp.arange(n, dtype=jnp.float32),
    }
    opt_state = {"step": jnp.full((n,), 7, jnp.int32)}
    directory = str(tmp_path / "run")
    ckpt.save_run(directory, params, opt_state, step=5, per_node_files=True)
    params2, opt2, step = ckpt.restore_run(directory)
    assert step == 5
    _assert_tree_equal(params, params2)
    _assert_tree_equal(opt_state, opt2)


def test_per_node_restore_validates_file_count(tmp_path):
    """A missing / renamed node file must raise, not silently restore a
    shorter (or re-indexed) node stack."""
    import os

    n = 12
    params = {"w": jnp.arange(n, dtype=jnp.float32)}
    opt_state = {"step": jnp.zeros((n,), jnp.int32)}
    directory = str(tmp_path / "run")
    ckpt.save_run(directory, params, opt_state, step=1, per_node_files=True)

    removed = os.path.join(directory, "node_05.npz")
    os.rename(removed, removed + ".bak")
    with pytest.raises(ValueError, match="num_nodes"):
        ckpt.restore_run(directory)
    os.rename(removed + ".bak", removed)
    ckpt.restore_run(directory)          # intact set restores fine

    # a gap with the right *count* (hole + stray extra index) also raises
    os.rename(os.path.join(directory, "node_03.npz"),
              os.path.join(directory, "node_99.npz"))
    with pytest.raises(ValueError, match="contiguous"):
        ckpt.restore_run(directory)


# ---------------------------------------------------------------------------
# Crash safety (docs/fault_model.md): atomic writes, checksums, history
# ---------------------------------------------------------------------------
def _tiny_run(n=4):
    params = {"w": jnp.arange(n, dtype=jnp.float32)[:, None] * jnp.ones((1, 3))}
    opt_state = {"step": jnp.full((n,), 7, jnp.int32)}
    return params, opt_state


def test_truncated_file_raises_named_corrupt_error(tmp_path):
    """Regression for the pre-atomic save: a torn/truncated checkpoint
    file must raise ``CheckpointCorruptError`` naming the file and the
    remedy, never load garbage or crash opaquely inside np.load."""
    import os

    params, opt_state = _tiny_run()
    directory = str(tmp_path / "run")
    ckpt.save_run(directory, params, opt_state, step=3, per_node_files=True)

    victim = os.path.join(directory, "node_02.npz")
    payload = open(victim, "rb").read()
    with open(victim, "wb") as f:
        f.write(payload[: len(payload) // 2])       # truncate
    with pytest.raises(ckpt.CheckpointCorruptError) as exc:
        ckpt.restore_run(directory)
    assert "node_02.npz" in str(exc.value)
    assert "earlier complete one" in str(exc.value)

    # same size but flipped content: the CRC32 check catches it
    with open(victim, "wb") as f:
        f.write(payload[:100] + bytes([payload[100] ^ 0xFF]) + payload[101:])
    with pytest.raises(ckpt.CheckpointCorruptError, match="CRC32"):
        ckpt.restore_run(directory)

    with open(victim, "wb") as f:                   # repaired: loads again
        f.write(payload)
    ckpt.restore_run(directory)


def test_save_is_atomic_no_tmp_left_behind(tmp_path):
    """Every write goes through tmp + rename: after a save the directory
    holds only final files, and re-saving overwrites via rename (an
    interrupted re-save can never tear the existing checkpoint)."""
    params, opt_state = _tiny_run()
    directory = str(tmp_path / "run")
    ckpt.save_run(directory, params, opt_state, step=1)
    ckpt.save_run(directory, params, opt_state, step=2)
    import os

    leftovers = [f for f in os.listdir(directory) if ".tmp." in f]
    assert leftovers == [], f"temp files left behind: {leftovers}"
    _, _, step = ckpt.restore_run(directory)
    assert step == 2


def test_history_layout_resume_and_pruning(tmp_path):
    """save_run_step's step_XXXXXXXX/ history: find_resumable resolves
    the newest complete entry, skips torn/incomplete ones (crash
    mid-save), restore_run delegates from the root, and keep_last
    prunes oldest-first."""
    import os

    params, opt_state = _tiny_run()
    root = str(tmp_path / "hist")
    for s in (2, 4, 6):
        d = ckpt.save_run_step(
            root, params, opt_state, step=s, keep_last=3)
        assert d == ckpt.step_dir(root, s) and os.path.isdir(d)
    assert ckpt.find_resumable(root) == ckpt.step_dir(root, 6)
    # the root itself restores: delegation to the newest complete entry
    _, _, step = ckpt.restore_run(root)
    assert step == 6

    # crash mid-save of step 8: ckpt.json (written last) never landed
    torn = ckpt.step_dir(root, 8)
    os.makedirs(torn)
    with open(os.path.join(torn, "params.npz"), "wb") as f:
        f.write(b"half a checkpoint")
    assert ckpt.find_resumable(root) == ckpt.step_dir(root, 6)

    # newest *complete-looking* entry fails its checksum: fall back
    with open(os.path.join(ckpt.step_dir(root, 6), "params.npz"), "wb") as f:
        f.write(b"also torn")
    assert ckpt.find_resumable(root) == ckpt.step_dir(root, 4)
    _, _, step = ckpt.restore_run(root)
    assert step == 4

    # keep_last=2 prunes the oldest complete entries on the next save
    ckpt.save_run_step(root, params, opt_state, step=10, keep_last=2)
    kept = sorted(f for f in os.listdir(root) if f.startswith("step_"))
    assert kept == ["step_00000008", "step_00000010"]
    assert ckpt.find_resumable(root) == ckpt.step_dir(root, 10)


def test_find_resumable_empty_and_missing(tmp_path):
    import os

    assert ckpt.find_resumable(str(tmp_path / "nope")) is None
    empty = str(tmp_path / "empty")
    os.makedirs(empty)
    assert ckpt.find_resumable(empty) is None


@pytest.mark.parametrize("per_node_files", [False, True])
def test_stacked_state_roundtrip(tmp_path, per_node_files):
    cfg = get_smoke_config("internlm2_1_8b")
    model = Model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    spec = dt.make_spec(mesh, cfg, multi_pod=False)
    # fake a 4-node run on the single local device: stacked state only
    spec = dataclasses.replace(spec, num_nodes=4)
    opt = sgd(0.1, momentum=0.9)
    params = dt.init_stacked_params(model, spec, seed=3)
    # distinct per-node values so a node-axis transposition would fail
    params = jax.tree.map(
        lambda a: a + jnp.arange(4, dtype=a.dtype).reshape(
            (4,) + (1,) * (a.ndim - 1))
        if jnp.issubdtype(a.dtype, jnp.floating) else a,
        params,
    )
    opt_state = dt.init_stacked_opt_state(opt, model, spec)

    directory = str(tmp_path / "run")
    ckpt.save_run(directory, params, opt_state, step=17,
                  per_node_files=per_node_files)
    params2, opt_state2, step = ckpt.restore_run(directory)
    assert step == 17
    _assert_tree_equal(params, params2)
    _assert_tree_equal(opt_state, opt_state2)
    assert float(dt.consensus_distance(params2)) == pytest.approx(
        float(dt.consensus_distance(params)))
