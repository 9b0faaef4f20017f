"""On-chip smoke run of the MATCHA trainer (TPU v5e).

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # the 4-node gossip path, 4 chips

Default: trains internlm2-1.8B at its published widths, cut to 8 of 24
layers, as one node (``--mode local``) for a few steps through
``repro.launch.train.main``, then runs the compiled gossip-axpy kernel
at the model's full-width leaf shapes against its jnp reference.

``--four-chips``: only the 4-node path. MATCHA (ring, CB 0.5, masked
gossip) at 4 of 24 layers, one node per chip, then one masked exchange
on the trained replicas over the four chips against the dense mixing
oracle ``W = I - alpha * sum_j b_j L_j``.

Everything runs in this one process. Any failure exits non-zero before
the last line, which is ``{"ok": true, "device": {...}}`` on success.
Without a TPU (or with ``JAX_PLATFORMS=cpu``) it fails.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ONE_CHIP_ARGV = [
    "--arch", "internlm2_1_8b", "--preset", "small", "--nodes", "1",
    "--mode", "local", "--steps", "6", "--batch-per-node", "4",
    "--seq", "128",
]
# 4 layers: the masked step's gossip temporaries (about 10 GB per chip
# whatever the depth) leave room for no more within 90% of HBM.
# 6 steps: the last step's schedule row activates a matching.
FOUR_CHIP_ARGV = [
    "--arch", "internlm2_1_8b", "--preset", "small", "--layers", "4",
    "--nodes", "4", "--graph", "ring", "--mode", "matcha",
    "--budget", "0.5", "--gossip-mode", "masked", "--steps", "6",
    "--batch-per-node", "4", "--seq", "128",
]
# full-width leaves of internlm2-1.8B (embedding/head, ffn, norm) and a
# ragged one that the kernel wrapper pads
KERNEL_SHAPES = [(92544, 2048), (2048, 8192), (2048,), (1000, 37)]


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def train(argv, compile_s):
    import jax
    import numpy as np

    from repro.launch import train as train_lib

    before = sum(compile_s)
    t0 = time.perf_counter()
    out = train_lib.main(argv)
    wall = time.perf_counter() - t0
    rows = out["rows"]
    losses = [r["loss"] for r in rows]
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    first, last = rows[0], rows[-1]
    step_ms = (last["wall"] - first["wall"]) / (last["step"] - first["step"])
    stats = jax.devices()[0].memory_stats() or {}
    print(f"train: {wall:.1f} s in main, backend compile "
          f"{sum(compile_s) - before:.1f} s, losses {losses}")
    print(f"train: step ms after warm-up {step_ms * 1e3:.1f} "
          f"(steps {first['step'] + 1}..{last['step']}, host clock, "
          "includes the host's synthetic batches)")
    print(f"train: peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
          f"(device 0)")
    return out


def one_chip(compile_s) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.kernels.ref import gossip_axpy_ref

    out = train(ONE_CHIP_ARGV, compile_s)
    check(out["plan"] is None, "one-node run built a topology")
    del out

    alpha = 0.3
    apply = jax.jit(lambda x, y: ops.gossip_apply(x, y, alpha))
    ref = jax.jit(lambda x, y: gossip_axpy_ref(x, y, alpha))
    for i, shape in enumerate(KERNEL_SHAPES):
        kx, ky = jax.random.split(jax.random.key(i))
        x = jax.random.normal(kx, shape, jnp.float32)
        y = jax.random.normal(ky, shape, jnp.float32)
        text = apply.lower(x, y).as_text()
        check("tpu_custom_call" in text,
              f"gossip_apply{shape} lowered without the Pallas kernel")
        got, want = apply(x, y), ref(x, y)
        err = float(jnp.max(jnp.abs(got - want)))
        print(f"kernel: gossip_axpy {shape} max |kernel - ref| {err:.3e}")
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


def four_chips(compile_s) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.dist.gossip import mix_dense, mix_matchings_masked

    out = train(FOUR_CHIP_ARGV, compile_s)
    params, plan, spec = out["params"], out["plan"], out["spec"]
    last = np.asarray(out["bits"], np.float32)
    check(last.sum() > 0, f"last step activates no matching: {last}")
    nodes = spec.nodes_axis

    def body(stacked, b):
        local = jax.tree.map(lambda a: a[0], stacked)
        mixed = mix_matchings_masked(
            local, plan.alpha, plan.permutations, b, spec.node_info)
        return jax.tree.map(lambda a: a[None], mixed)

    exchange = jax.jit(jax.shard_map(
        body, mesh=spec.mesh, in_specs=(P(nodes), P()), out_specs=P(nodes),
        axis_names=set(spec.node_axes), check_vma=False,
    ))
    # node-sharded output: a replicated one would hold every replica of
    # the leaf on each chip
    dense = jax.jit(mix_dense, out_shardings=NamedSharding(spec.mesh, P(nodes)))

    @jax.jit
    def gaps(x, got, want):
        return (jnp.max(jnp.abs(got - want)), jnp.max(jnp.abs(want - x)),
                jnp.max(jnp.abs(x)))

    # fp32: both sides round a few times at the scale of x
    tol = 1e-6
    # The last step has just mixed its matchings' pairs; at alpha 1/2 a
    # second exchange over them moves nothing, so every matching is
    # exchanged once more as well.
    for bits in (last, np.ones_like(last)):
        W = np.eye(spec.num_nodes) - plan.alpha * sum(
            b * m.laplacian() for b, m in zip(bits, plan.matchings))
        W = jnp.asarray(W, jnp.float32)
        with jax.set_mesh(spec.mesh):
            got = exchange(params, jnp.asarray(bits))
            worst = moved_max = 0.0
            # leaf by leaf: the oracle holds all four replicas of a leaf
            for x, g in zip(jax.tree.leaves(params), jax.tree.leaves(got)):
                err, moved, xmax = map(float, gaps(x, g, dense(x, W)))
                check(err <= tol * max(xmax, 1.0),
                      f"masked exchange {x.shape}: |got - dense| {err:.3e}")
                worst, moved_max = max(worst, err), max(moved_max, moved)
            del got
        print(f"exchange: alpha {plan.alpha:.4f} bits {bits.tolist()}, "
              f"max |masked - dense| {worst:.3e}, "
              f"max |dense - x| {moved_max:.3e}")
    check(moved_max > 100 * tol,
          "the exchange moved the replicas too little to check it")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-node gossip path (needs 4 chips)")
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    from repro.launch import runtime

    import jax

    runtime.init_compile_cache()
    print(runtime.device_line())
    devs = jax.devices()
    want = 4 if args.four_chips else 1
    try:
        check(devs[0].platform == "tpu", f"no TPU: jax runs on "
                                         f"{devs[0].platform}")
        check(len(devs) >= want, f"{want} chips needed, {len(devs)} found")
        from repro.kernels import ops

        check(ops.resolve_mode("auto", off_tpu="interpret") == "pallas",
              "the gossip hot path does not resolve to the compiled kernel")
        compile_s = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, secs, **_: compile_s.append(secs)
            if event == "/jax/core/compile/backend_compile_duration" else None
        )
        (four_chips if args.four_chips else one_chip)(compile_s)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
