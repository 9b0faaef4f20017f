"""The comparison that decides ``correct``.

Three readings of the first three steps, of the program and of the
plain reference alike, per node:
  loss     each step's loss;
  grad     per leaf, the norm of the first gradient as the optimizer
           got it (SGD-momentum's buffer after one step from zero);
  change   per leaf, the norm of the parameters' change over the three
           steps.
Numbers compared, each the worst over steps, nodes and leaves:
  loss_gap    |loss - ref| / |ref|;
  grad_gap    |norm - ref norm| / max(ref norm of the leaf, median leaf's);
  change_gap  the same for the change, over the leaves whose reference
              gradient is at least a thousandth of the median leaf's
              (below that a leaf moves by round-off alone).
"""
from __future__ import annotations

import functools
import importlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.reference.common import DOTS, mix, partner_table, sgd_momentum

STEPS = 3
NAMES = ("loss_gap", "grad_gap", "change_gap")
MOVED = 1e-3
REF_ROWS = 2               # most rows of a node's batch per reference call


def _node_norm(a):
    a = a.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(a * a, axis=tuple(range(1, a.ndim))))


_norms = jax.jit(lambda leaves: [_node_norm(a) for a in leaves])


@functools.partial(jax.jit, static_argnums=(2, 3))
def _change(x, key, index, path):
    x0 = weights.make_leaf(key, index, path, x.shape[1:], x.dtype)
    return _node_norm(x - x0[None])


def leaf_norms(tree) -> dict:
    """Per leaf path, the norm of each node's slice (leading dim)."""
    norms = _norms(jax.tree.leaves(tree))
    return {p: np.asarray(n, np.float64)
            for p, n in zip(weights.leaf_paths(tree), norms)}


def change_norms(stacked, seed: int) -> dict:
    """Per leaf, each node's norm of (leaf - its initial value), the
    initial value made again leaf by leaf from the seed."""
    key = weights.base_key(seed)
    paths = weights.leaf_paths(stacked)
    return {path: np.asarray(_change(leaf, key, i, path), np.float64)
            for i, (path, leaf) in enumerate(
                zip(paths, jax.tree.leaves(stacked)))}


def row_blocks(rows: int) -> list:
    """Equal blocks of at most ``REF_ROWS`` rows: the mean over tokens is
    the mean of the blocks' means, and a block's float32 activations fit
    on the chip where the whole batch's would not."""
    n = max(d for d in range(1, min(rows, REF_ROWS) + 1) if rows % d == 0)
    return [slice(i, i + n) for i in range(0, rows, n)]


_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
_scale = jax.jit(lambda a, s: jax.tree.map(lambda x: x * s, a),
                 donate_argnums=0)


def reference_readings(family: str, config: dict, traffic: dict, abstract,
                       seed: int, batches, bits_rows, devices,
                       precision: str = "float32") -> dict:
    """The reference's readings: each node's replica on its own device,
    three steps of SGD-momentum, and after each step the mixing over the
    traffic's matchings with that step's bits."""
    ref = importlib.import_module(f"bench.reference.{family}")
    dot = DOTS[precision]
    nodes = int(traffic["nodes"])
    lr, mom = float(traffic["lr"]), float(traffic["momentum"])
    key = weights.base_key(seed)
    with jax.default_matmul_precision("highest"):
        p0 = jax.jit(lambda k: weights.replica(k, abstract))(key)
        ps = [jax.device_put(p0, d) for d in devices[:nodes]]
        del p0
        vs = [jax.tree.map(jnp.zeros_like, p) for p in ps]
        grad_of = jax.jit(jax.value_and_grad(
            lambda p, t, l: ref.loss(p, t, l, config, dot)))
        step = jax.jit(lambda p, v, g: sgd_momentum(p, v, g, lr, mom),
                       donate_argnums=(0, 1))
        partners = partner_table(traffic.get("matchings", []), nodes)
        alpha = float(traffic.get("alpha", 0.0))
        losses, first = [], []
        for k in range(STEPS):
            tokens, labels = batches[k]
            row = []
            # dispatch every node before reading any: the nodes' devices
            # work at once
            for i in range(nodes):
                blocks = row_blocks(tokens[i].shape[0])
                loss, g = 0.0, None
                for b in blocks:
                    t = jax.device_put(tokens[i][b], devices[i])
                    l_ = jax.device_put(labels[i][b], devices[i])
                    loss_b, g_b = grad_of(ps[i], t, l_)
                    loss = loss + loss_b
                    g = g_b if g is None else _add(g, g_b)
                    del g_b
                if len(blocks) > 1:
                    loss, g = loss / len(blocks), _scale(g, 1.0 / len(blocks))
                row.append(loss)
                if k == 0:
                    first.append(_norms(
                        [a[None] for a in jax.tree.leaves(g)]))
                ps[i], vs[i] = step(ps[i], vs[i], g)
                del g
            losses.append([float(x) for x in row])
            if nodes > 1 and np.any(bits_rows[k]):
                flat = [jax.tree.leaves(p) for p in ps]
                treedef = jax.tree.structure(ps[0])
                mixed = [mix([f[j] for f in flat], partners, bits_rows[k],
                             alpha) for j in range(len(flat[0]))]
                ps = [jax.tree.unflatten(treedef, [m[i] for m in mixed])
                      for i in range(nodes)]
        paths = weights.leaf_paths(ps[0])
        grads = {p: [float(first[i][j][0]) for i in range(nodes)]
                 for j, p in enumerate(paths)}
        change = {}
        for i in range(nodes):
            for path, n in change_norms(
                    jax.tree.map(lambda a: a[None], ps[i]), seed).items():
                change.setdefault(path, []).append(float(n[0]))
    return {"loss": np.asarray(losses),
            "grad": {p: np.asarray(v) for p, v in grads.items()},
            "change": {p: np.asarray(v) for p, v in change.items()}}


def _leaf_gap(got: dict, want: dict, keep=None) -> float:
    worst = 0.0
    paths = list(want)
    nodes = len(next(iter(want.values())))
    for i in range(nodes):
        ref = np.asarray([want[p][i] for p in paths])
        floor = float(np.median(ref))
        for p, r in zip(paths, ref):
            if keep is not None and not keep[p][i]:
                continue
            gap = abs(float(got[p][i]) - r) / max(r, floor, 1e-30)
            if not np.isfinite(gap):
                return float("inf")
            worst = max(worst, gap)
    return worst


def compare(prog: dict, ref: dict) -> dict:
    """The three numbers compared (see the module docstring)."""
    loss = np.max(np.abs(np.asarray(prog["loss"]) - ref["loss"])
                  / np.abs(ref["loss"]))
    if not np.isfinite(loss):
        loss = float("inf")
    paths = list(ref["grad"])
    nodes = len(ref["grad"][paths[0]])
    keep = {p: [None] * nodes for p in paths}
    for i in range(nodes):
        med = float(np.median([ref["grad"][p][i] for p in paths]))
        for p in paths:
            keep[p][i] = ref["grad"][p][i] >= MOVED * med
    return {
        "loss_gap": float(loss),
        "grad_gap": _leaf_gap(prog["grad"], ref["grad"]),
        "change_gap": _leaf_gap(prog["change"], ref["change"], keep),
    }


def load_limits(bench_dir: Path, workload: str) -> dict:
    """The cell's limits: each number compared and its limit. A number
    that the file leaves out is printed and not compared."""
    path = bench_dir / "limits" / f"{workload}.json"
    with open(path) as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, [[name, value, limit], ...]) over the numbers the cell's
    limits file compares, and the plan check (limit 0)."""
    limits = dict(limits)
    if "plan_mismatch" in numbers:
        limits["plan_mismatch"] = 0.0
    rows, ok = [], True
    for name in [n for n in list(NAMES) + ["plan_mismatch"] if n in limits]:
        value = numbers[name]
        rows.append([name, value, limits[name]])
        ok = ok and bool(np.isfinite(value)) and value <= limits[name]
    return ok, rows
