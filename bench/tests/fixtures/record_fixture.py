"""Record the device-trace fixture of ``test_bench_trace.py``.

    python3 bench/tests/fixtures/record_fixture.py OUT_DIR   # on a TPU

Two steps of a toy jitted step with the benchmark's scope names
(``fwd_bwd``, ``optimizer``, ``gossip``; with two devices or more, a
ppermute under ``gossip/matching0``), traced by the profiler; writes the
step's compiled HLO text beside the trace and prints the trace's planes,
lines and first events.
"""
import glob
import os
import shutil
import sys

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def main(out: str) -> None:
    shutil.rmtree(out, ignore_errors=True)
    n = len(jax.devices())
    mesh = Mesh(jax.devices(), ("data",))

    def body(w, x):
        with jax.named_scope("fwd_bwd"):
            y = jnp.tanh(x[0] @ w[0])
            g = jax.grad(lambda w_: jnp.sum(jnp.tanh(x[0] @ w_)))(w[0])
        with jax.named_scope("optimizer"):
            w2 = w[0] - 0.01 * g
        with jax.named_scope("gossip"):
            if n > 1:
                with jax.named_scope("gossip/matching0"):
                    p = jax.lax.ppermute(w2, "data", [(i, i ^ 1) for i in range(n)])
                w2 = w2 + 0.5 * (p - w2)
            else:
                w2 = w2 * 0.999 + 0.001
        return w2[None], y.sum()[None]

    step = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("data"), P("data")),
                                 out_specs=(P("data"), P("data")), check_vma=False))
    w = jax.device_put(jnp.ones((n, 512, 512)) / 512, NamedSharding(mesh, P("data")))
    x = jax.device_put(jnp.ones((n, 256, 512)), NamedSharding(mesh, P("data")))
    w, _ = step(w, x); jax.block_until_ready(w)
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench/window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench/dispatch"):
                w, y = step(w, x)
            with jax.profiler.TraceAnnotation("bench/wait"):
                jax.block_until_ready(y)
    jax.profiler.stop_trace()
    open(os.path.join(out, "step.hlo.txt"), "w").write(step.lower(w, x).compile().as_text())
    for f in glob.glob(out + "/**/*.xplane.pb", recursive=True):
        print(f, os.path.getsize(f))
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(f)
        for plane in pd.planes:
            print("PLANE", plane.name, [l.name for l in plane.lines])
            for line in plane.lines:
                for i, ev in enumerate(line.events):
                    if i >= 6: break
                    print("   ", line.name, "|", ev.name, ev.start_ns, ev.duration_ns, {k: (v if not isinstance(v, bytes) else v[:80]) for k, v in dict(ev.stats).items()})


if __name__ == "__main__":
    main(sys.argv[1])
