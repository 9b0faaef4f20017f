"""One shared jaxpr walk for every static check in ``repro.analysis``.

This is the traversal that used to live (twice, copy-pasted) inside the
subprocess bodies of ``tests/test_stream_fsdp.py``.  It descends through
every sub-jaxpr a traced step can hide — ``shard_map`` bodies, ``scan``
bodies, ``remat``/``checkpoint`` closures, ``custom_vjp`` call jaxprs and
``pjit`` calls — and hands each equation to the caller together with an
:class:`EqnContext` describing *where* in the program it sits (inside a
manual shard_map region or not, multiplied by how many scan trips execute
it).  The collective inventory, the memory-ladder rule and the dtype lint
are all folds over :func:`iter_eqns`.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr

__all__ = [
    "EqnContext",
    "iter_eqns",
    "max_fp_intermediate",
    "source_frames",
    "sub_jaxprs",
    "to_closed_jaxpr",
]


def sub_jaxprs(params: dict) -> Iterator[Jaxpr]:
    """Yield every Jaxpr reachable from one equation's params.

    Sub-jaxprs appear as ``Jaxpr`` or ``ClosedJaxpr`` param values, either
    bare (``pjit``'s ``jaxpr``, ``scan``'s ``jaxpr``, remat's ``jaxpr``) or
    inside lists/tuples (``custom_vjp``'s branches, ``cond``'s branches).
    """
    for v in params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for w in vs:
            if isinstance(w, ClosedJaxpr):
                yield w.jaxpr
            elif isinstance(w, Jaxpr):
                yield w


def to_closed_jaxpr(obj: Any, *args: Any) -> ClosedJaxpr:
    """Normalize to a ``ClosedJaxpr``.

    Accepts a ``ClosedJaxpr``, a bare ``Jaxpr`` (wrapped with no consts),
    or a callable — in which case ``*args`` are example arguments and the
    callable is traced with ``jax.make_jaxpr``.
    """
    if isinstance(obj, ClosedJaxpr):
        return obj
    if isinstance(obj, Jaxpr):
        return ClosedJaxpr(obj, ())
    if callable(obj):
        return jax.make_jaxpr(obj)(*args)
    raise TypeError(f"cannot interpret {type(obj).__name__} as a jaxpr")


@dataclass(frozen=True)
class EqnContext:
    """Where an equation sits inside the traced program.

    in_manual   True iff the eqn is inside (strictly below) a ``shard_map``
                — i.e. its shapes are per-device block shapes, which is
                what the memory-ladder and byte checks care about.
    scan_trips  Product of the ``length`` params of every enclosing
                ``scan``: how many times this eqn executes per step call.
    path        Primitive names of the enclosing equations, outermost
                first (e.g. ``("pjit", "shard_map", "scan")``).
    """

    in_manual: bool = False
    scan_trips: int = 1
    path: tuple = ()


def _is_shard_map(eqn) -> bool:
    return "shard_map" in str(eqn.primitive)


def iter_eqns(jaxpr, ctx: EqnContext | None = None):
    """Depth-first pre-order walk yielding ``(eqn, EqnContext)`` pairs.

    ``jaxpr`` may be anything :func:`to_closed_jaxpr` accepts (already
    traced).  The yielded context describes the equation itself; its
    sub-jaxprs are visited with ``in_manual`` set if the equation is a
    ``shard_map`` and ``scan_trips`` multiplied by a scan's ``length``.
    """
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    if ctx is None:
        ctx = EqnContext()
    for eqn in jaxpr.eqns:
        yield eqn, ctx
        name = str(eqn.primitive)
        trips = ctx.scan_trips
        if name == "scan":
            trips *= int(eqn.params.get("length", 1))
        sub_ctx = replace(
            ctx,
            in_manual=ctx.in_manual or _is_shard_map(eqn),
            scan_trips=trips,
            path=ctx.path + (name,),
        )
        for sub in sub_jaxprs(eqn.params):
            yield from iter_eqns(sub, sub_ctx)


def max_fp_intermediate(step: Callable, args: tuple) -> list:
    """Largest floating-point intermediate (in elements) of a traced step.

    Traces ``step(*args)`` and scans every equation *inside* shard_map
    regions (per-device block shapes; equations outside manual regions
    carry global shapes and ``shard_map`` eqns themselves re-emit their
    global outputs).  Returns ``[num_elements, (primitive, shape)]`` —
    indexable, matching the tuple-ish shape the memory-ladder tests
    historically used.
    """
    closed = to_closed_jaxpr(step, *args)
    best: list = [0, None]
    for eqn, ctx in iter_eqns(closed):
        if not ctx.in_manual or _is_shard_map(eqn):
            continue
        for ov in eqn.outvars:
            aval = getattr(ov, "aval", None)
            if aval is None or not hasattr(aval, "shape"):
                continue
            if not jnp.issubdtype(aval.dtype, jnp.floating):
                continue
            n = int(np.prod(aval.shape)) if aval.shape else 1
            if n > best[0]:
                best[0] = n
                best[1] = (str(eqn.primitive), tuple(aval.shape))
    return best


def source_frames(eqn) -> tuple:
    """User-code frames of an equation as ``(file, function, line)`` tuples.

    Best-effort: returns ``()`` when jax carries no source info (e.g.
    synthetic jaxprs built by tests).  Innermost frame first — the frame
    whose function actually issued the primitive leads.
    """
    si = getattr(eqn, "source_info", None)
    if si is None or getattr(si, "traceback", None) is None:
        return ()
    try:
        from jax._src import source_info_util

        # function_name is the qualified name on Python >= 3.11
        # ("outer.<locals>.leaf"); the frames carry the bare one
        return tuple(
            (str(fr.file_name), str(fr.function_name).rsplit(".", 1)[-1],
             int(fr.start_line))
            for fr in source_info_util.user_frames(si.traceback)
        )
    except Exception:
        return ()
