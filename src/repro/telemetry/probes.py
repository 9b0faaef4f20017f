"""Measured communication probes + per-step metrics records.

The train step is one fused XLA executable, so a host clock cannot see
*inside* it. What it can see, honestly, is:

* the whole fenced step (``timers.timed_step``),
* each runtime phase, when the driver opts into the *phased* executors
  (``repro.dist.decen_train.make_phased_train_step`` /
  ``repro.dist.fsdp.make_phased_train_step`` — separate jitted
  executables per phase, fenced between),
* and isolated collectives, re-issued here as standalone probe
  executables on representative payloads: one ppermute per matching
  (:func:`measure_matchings`) and the fsdp all-gather / reduce-scatter
  pair (:func:`measure_fsdp_collectives`).

Probe payloads mirror the real exchange: a matching probe moves one
node's full per-matching gossip payload (``per_node_elements`` fp32 —
the bucket total for replicated runs; the fsdp runtime moves the same
total split 1/S per device), so a probe's wall time is the measured
analogue of the paper's "one unit per activated matching" link time.
All durations are milliseconds; summaries report mean/p50/p95 over
``iters`` fenced repetitions after ``warmup`` uncounted ones (the first
call pays compilation).

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


def measure_fsdp_collectives(
    spec,
    layout,
    *,
    timer: Optional[StepTimer] = None,
    iters: int = 3,
    warmup: int = 1,
    seed: int = 0,
) -> Dict[str, Dict[str, float]]:
    """Measured cost of the two fsdp sharding collectives, isolated.

    ``"gather"``: all-gather every bucket shard over the ``"shard"``
    axis (the step's parameter re-materialization), consumed by a
    scalar sum so XLA cannot drop it. ``"reduce_scatter"``: one
    ``psum_scatter`` per bucket on same-shaped fp32 payloads (the grad
    path's transpose). Both run on ``(nodes, S, size // S)`` buffers
    matching ``layout.shard_sizes``. Returns
    ``{"gather": summary, "reduce_scatter": summary}`` (ms summaries as
    :func:`summarize_ms`).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    timer = timer or StepTimer()
    nodes_ax = spec.nodes_axis
    manual = set(spec.node_axes) | {"shard"}
    n, s = layout.num_nodes, layout.num_shards
    key = jax.random.key(seed)
    shards = tuple(
        jax.device_put(
            jax.random.normal(k, (n, s, sz), jnp.float32),
            NamedSharding(spec.mesh, P(nodes_ax, "shard")),
        )
        for k, sz in zip(
            jax.random.split(key, len(layout.shard_sizes)),
            layout.shard_sizes,
        )
    )

    def gather_body(*bufs):
        total = jnp.float32(0.0)
        for b in bufs:
            full = jax.lax.all_gather(b[0, 0], "shard", tiled=True)
            total = total + jnp.sum(full)
        return total[None, None]

    def rs_body(*bufs):
        out = []
        for b in bufs:
            r = jax.lax.psum_scatter(
                b[0, 0], "shard", scatter_dimension=0, tiled=True
            )
            out.append(r[None, None])
        return tuple(out)

    pspec = tuple(P(nodes_ax, "shard") for _ in shards)
    gather = jax.jit(jax.shard_map(
        gather_body, mesh=spec.mesh, in_specs=pspec,
        out_specs=P(nodes_ax, "shard"), axis_names=manual,
        check_vma=False,
    ))
    rs = jax.jit(jax.shard_map(
        rs_body, mesh=spec.mesh, in_specs=pspec, out_specs=pspec,
        axis_names=manual,
        check_vma=False,
    ))
    out = {}
    out["gather"] = _probe_loop(
        timer, "gather", lambda: gather(*shards),
        iters=iters, warmup=warmup, cat="comm", tid=1,
    )
    out["reduce_scatter"] = _probe_loop(
        timer, "reduce_scatter", lambda: rs(*shards),
        iters=iters, warmup=warmup, cat="comm", tid=1,
    )
    return out


# ---------------------------------------------------------------------------
# Fault events
# ---------------------------------------------------------------------------
def fault_event(recorder, *, step: int, kind: str, **extras) -> None:
    """Record one injected-fault event in the trace stream.

    ``kind`` names the fault (``"link_drop"``, ``"straggler"``,
    ``"crash"``); ``extras`` carry its parameters (dropped-exchange
    count, delay units, ...). Events land with ``cat="fault"`` on the
    comm thread lane as zero-duration instants, so a Perfetto view of a
    faulted run shows exactly where the schedule injected what. A
    ``None`` recorder no-ops — the untraced loop pays nothing."""
    if recorder is None:
        return
    from repro.telemetry.trace import TraceEvent

    recorder.record(TraceEvent(
        name=f"fault/{kind}", cat="fault", ts_us=recorder.now_us(),
        dur_us=0.0, step=int(step), tid=1, args=dict(extras),
    ))


# ---------------------------------------------------------------------------
# Per-step metrics
# ---------------------------------------------------------------------------
def step_metrics(
    *,
    step: int,
    step_ms: float,
    comm_ms: float,
    gossip_mode: str,
    comm_bytes: int = 0,
    phase_ms: Optional[Dict[str, float]] = None,
) -> Dict[str, Any]:
    """One step's measured metrics record (the ``--trace`` log line and
    CSV columns).

    ``step_ms``    fenced whole-step wall time.
    ``comm_ms``    the step's communication time: the measured
                   ``gossip`` phase when the phased executor ran,
                   otherwise the per-matching probe means summed over
                   the activated matchings.
    ``comm_bytes`` per-node bytes the step's exchange moved
                   (``analysis.bytes_model`` per-matching bytes x
                   activated matchings) — modeled, marked as such in
                   the docs.
    ``overlap_ratio``  fraction of the step's comm that does NOT extend
                   the step: 0 by construction for sequential modes
                   (the exchange serializes after the fwd/bwd); for
                   ``overlap`` mode, ``min(comm_ms, step_ms) / step_ms``
                   — an upper bound on the hidden fraction, since the
                   probe-measured comm either fits under the compute or
                   extends the step.
    """
    step_ms = float(step_ms)
    comm_ms = float(comm_ms)
    overlapped = gossip_mode == "overlap"
    if step_ms > 0 and overlapped:
        overlap_ratio = min(comm_ms, step_ms) / step_ms
    else:
        overlap_ratio = 0.0
    out = {
        "step": int(step),
        "step_ms": round(step_ms, 4),
        "comm_ms": round(comm_ms, 4),
        "comm_fraction": round(comm_ms / step_ms, 4) if step_ms > 0 else 0.0,
        "overlap_ratio": round(overlap_ratio, 4),
        "comm_bytes": int(comm_bytes),
    }
    if phase_ms:
        for k, v in phase_ms.items():
            out[f"{k}_ms"] = round(float(v), 4)
    return out


def format_metrics_line(m: Dict[str, Any]) -> str:
    """Human-readable one-liner for the driver log."""
    parts = [
        f"trace step {m['step']:4d}",
        f"step {m['step_ms']:8.2f} ms",
        f"comm {m['comm_ms']:7.2f} ms ({100 * m['comm_fraction']:.0f}%)",
        f"overlap {m['overlap_ratio']:.2f}",
        f"comm_bytes {m['comm_bytes']}",
    ]
    extra = [k for k in m if k.endswith("_ms") and k not in
             ("step_ms", "comm_ms")]
    if extra:
        parts.append(" ".join(f"{k[:-3]} {m[k]:.2f}" for k in sorted(extra)))
    return "  ".join(parts)
