"""Measured communication probes.

The train step is one fused XLA executable; the profiler's device trace
(``launch/train.py --trace``) is what sees inside it. What a host clock
can time is an isolated collective, re-issued here as a standalone
probe executable on a representative payload: one ppermute per
matching (:func:`measure_matchings`), which ``benchmarks/bench_comm_time``
reports beside the modeled units.

A matching probe moves one node's full per-matching gossip payload
(``per_node_elements`` fp32 — the bucket total for replicated runs; the
fsdp runtime moves the same total split 1/S per device), so a probe's
wall time is the measured analogue of the paper's "one unit per
activated matching" link time. All durations are milliseconds;
summaries report mean/p50/p95 over ``iters`` fenced repetitions after
``warmup`` uncounted ones (the first call pays compilation).

``repro.dist`` is imported lazily inside the probe builders — importing
:mod:`repro.telemetry` must never pull jax/dist machinery into a
process that only wants to read a trace file.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from repro.telemetry.timers import StepTimer


def summarize_ms(samples: Sequence[float]) -> Dict[str, float]:
    """mean/p50/p95 (milliseconds) + sample count of one probe's fenced
    repetitions."""
    arr = np.asarray(list(samples), dtype=np.float64)
    if arr.size == 0:
        return {"mean_ms": 0.0, "p50_ms": 0.0, "p95_ms": 0.0, "n": 0}
    return {
        "mean_ms": float(arr.mean()),
        "p50_ms": float(np.percentile(arr, 50)),
        "p95_ms": float(np.percentile(arr, 95)),
        "n": int(arr.size),
    }


def _probe_loop(timer: StepTimer, name: str, fn, *, iters: int,
                warmup: int, **event_args) -> Dict[str, float]:
    """warmup (uncounted, pays compile) + iters fenced repetitions."""
    import jax

    for _ in range(max(warmup, 0)):
        jax.block_until_ready(fn())
    samples = []
    for _ in range(max(iters, 1)):
        _, dur_ms = timer.measure(name, fn, **event_args)
        samples.append(dur_ms)
    return summarize_ms(samples)


def measure_matchings(
    plan,
    spec,
    *,
    per_node_elements: int,
    timer: Optional[StepTimer] = None,
    iters: int = 5,
    warmup: int = 1,
    seed: int = 0,
) -> List[Dict[str, Any]]:
    """Measured per-matching exchange time.

    For each matching j of ``plan`` this builds a standalone jitted
    ``shard_map`` that ppermutes a ``(num_nodes, per_node_elements)``
    fp32 buffer over the run's node axes with matching j's involution
    pairs — exactly the collective the gossip step issues for that
    matching — and times ``iters`` fenced runs. Returns one row per
    matching::

        {"matching": j, "bytes_per_node": 4 * per_node_elements,
         "mean_ms": ..., "p50_ms": ..., "p95_ms": ..., "n": iters}

    Events are recorded (cat ``"comm"``, tid 1, names
    ``gossip/matching{j}``) when ``timer`` is enabled. Must be called
    inside ``jax.set_mesh(spec.mesh)`` or with explicitly placed input —
    the probe builds its own input via ``jax.device_put``.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    timer = timer or StepTimer()
    info = spec.node_info
    n = spec.num_nodes
    s = int(getattr(spec, "num_shards", 1))
    per_node_elements = int(per_node_elements)
    # On an fsdp mesh the payload splits over "shard" like the runtime's
    # bucket shards: each device moves 1/S, the node still moves the
    # full per_node_elements per matching.
    if s > 1:
        per_node_elements += (-per_node_elements) % s
        shape = (n, s, per_node_elements // s)
        pspec = P(spec.nodes_axis, "shard")
        manual = set(spec.node_axes) | {"shard"}
    else:
        shape = (n, per_node_elements)
        pspec = P(spec.nodes_axis)
        manual = set(spec.node_axes)
    x = jax.device_put(
        jax.random.normal(jax.random.key(seed), shape, jnp.float32),
        NamedSharding(spec.mesh, pspec),
    )
    perms = np.asarray(plan.permutations)
    rows = []
    for j in range(perms.shape[0]):
        pairs = [(i, int(perms[j][i])) for i in range(n)]

        def body(v, _pairs=pairs):
            return jax.lax.ppermute(v, info.axis_name, _pairs)

        probe = jax.jit(jax.shard_map(
            body,
            mesh=spec.mesh,
            in_specs=pspec,
            out_specs=pspec,
            axis_names=manual,
            check_vma=False,
        ))
        summary = _probe_loop(
            timer, f"gossip/matching{j}", lambda p=probe: p(x),
            iters=iters, warmup=warmup, cat="comm", tid=1,
            bytes_per_node=4 * int(per_node_elements), matching=j,
        )
        rows.append({"matching": j,
                     "bytes_per_node": 4 * int(per_node_elements),
                     **summary})
    return rows
