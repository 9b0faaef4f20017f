"""Kernel-level static analyzer: below the jaxpr, into the pallas_call.

The collective/byte/ladder checks (``repro.analysis.checks``) treat a
``pallas_call`` equation as an opaque box. This module opens the box:
every kernel in ``repro.kernels`` declares a ``KERNEL_CONTRACT`` (the
kernel-level analogue of the dist layer's ``COLLECTIVE_CONTRACT``), and
the linter verifies the *traced* grid spec and kernel body against it —
nothing executes, nothing is allocated.

Per ``pallas_call`` equation (found by walking the jaxpr through pjit /
shard_map / scan / remat, ``traversal.iter_eqns``):

* grid arity vs the contract's named grid axes
  (``kernel-contract-mismatch``),
* every BlockSpec block shape divides its (padded) operand shape —
  the ops wrappers pad *before* calling, so an indivisible block is a
  wrapper bug, not a tail to mask (``block-shape-indivisible``),
* every index map, evaluated (vmapped ``eval_jaxpr``) over the full
  grid, lands in bounds: 0 <= idx_d <= array_d/block_d - 1
  (``index-map-out-of-bounds``); index maps must be static in the
  grid indices — one that reads a scalar-prefetch operand cannot be
  checked and is itself flagged (``index-map-not-static``),
* output writes are disjoint across grid points: two grid points may
  map to the same output block only if they differ solely in the
  contract's declared ``reduction_axes`` (``output-overlap-undeclared``),
* declared masked tails are guarded in the kernel body: a ragged
  ``kv_len``-style bound must appear as a live comparison against that
  literal (``masked-tail-guard-missing`` / ``masked-tail-guard-dead``);
  a scalar-prefetch-masked kernel must read the prefetched offsets and
  compare against them,
* accumulation dtype: scratch accumulators match the contract's
  ``acc_dtype``, and low-precision (bf16/fp16) operands are widened to
  fp32 somewhere before arithmetic (``acc-dtype-not-fp32``),
* a per-grid-step VMEM footprint model — double-buffered in/out blocks
  plus scratch — stays under the contract's ``vmem_limit_bytes`` and
  the 16 MiB hardware budget (``vmem-bound-exceeded``).

A case that traces no ``pallas_call`` at all (e.g. a wrapper silently
falling back to the reference path) is ``pallas-call-missing``.

Source-level companion check: :func:`check_interpret_literals` walks the
AST of every file under ``src/repro`` and flags a hardcoded
``interpret=True/False`` call argument outside ``kernels/ops.py``
(``hardcoded-interpret-mode``) — the backend/interpret decision belongs
to ``ops.resolve_mode`` alone.
"""

from __future__ import annotations

import ast
import itertools
import os
from typing import Sequence

import jax
import numpy as np
from jax.extend.core import Literal

from repro.analysis.checks import Violation
from repro.analysis.traversal import iter_eqns, to_closed_jaxpr

__all__ = [
    "KernelCallInfo",
    "check_interpret_literals",
    "find_pallas_calls",
    "lint_case",
    "lint_pallas_eqn",
    "vmem_footprint_bytes",
]

VMEM_BYTES = 16 * 2**20   # per-core VMEM hardware budget
_CMP_PRIMS = ("lt", "le", "gt", "ge", "eq", "ne")


# ---------------------------------------------------------------------------
# pallas_call discovery + normalized views
# ---------------------------------------------------------------------------
class KernelCallInfo:
    """Normalized view of one traced ``pallas_call`` equation."""

    def __init__(self, eqn):
        self.eqn = eqn
        gm = eqn.params["grid_mapping"]
        self.grid = tuple(int(g) for g in gm.grid)
        self.num_inputs = int(gm.num_inputs)
        self.num_outputs = int(gm.num_outputs)
        self.num_scratch = int(gm.num_scratch_operands)
        self.num_index = int(gm.num_index_operands)
        bms = tuple(gm.block_mappings)
        self.in_mappings = bms[: self.num_inputs]
        self.out_mappings = bms[self.num_inputs:]
        self.name = str(eqn.params.get("name_and_src_info", "pallas_call"))
        # kernel body: bare Jaxpr; invars are
        # [index/scalar-prefetch..., inputs..., outputs..., scratch...]
        self.body = eqn.params["jaxpr"]

    def scratch_avals(self):
        if not self.num_scratch:
            return ()
        return tuple(
            v.aval for v in self.body.invars[-self.num_scratch:]
        )


def find_pallas_calls(closed) -> list:
    """Every ``pallas_call`` reachable from a traced program, as
    :class:`KernelCallInfo` (walks pjit/shard_map/scan/remat bodies)."""
    out = []
    for eqn, _ctx in iter_eqns(to_closed_jaxpr(closed)):
        if str(eqn.primitive) == "pallas_call":
            out.append(KernelCallInfo(eqn))
    return out


# ---------------------------------------------------------------------------
# individual checks over one pallas_call
# ---------------------------------------------------------------------------
def _block_dims(bm) -> tuple:
    """(array_shape, block_shape, dtype) of one BlockMapping."""
    aval = bm.array_aval
    # block dims are ``pl.Blocked``/``pl.Element`` (carrying
    # ``block_size``) or ``pl.Squeezed`` (size 1)
    block = tuple(int(getattr(b, "block_size", 1)) for b in bm.block_shape)
    return tuple(aval.shape), block, aval.dtype


def check_contract_shape(info: KernelCallInfo, contract: dict, where: str):
    out = []
    want = tuple(contract["grid"])
    if len(info.grid) != len(want):
        out.append(Violation(
            "kernel-contract-mismatch",
            f"traced grid has {len(info.grid)} axes {info.grid}; contract "
            f"declares {len(want)} named axes {want}",
            where,
        ))
    for ax in contract.get("reduction_axes", ()):
        if not 0 <= ax < len(info.grid):
            out.append(Violation(
                "kernel-contract-mismatch",
                f"declared reduction axis {ax} outside the "
                f"{len(info.grid)}-axis grid",
                where,
            ))
    return out


def check_block_divisibility(info: KernelCallInfo, where: str):
    """Block shapes must divide the (already padded) operand shapes."""
    out = []
    for role, bms in (("in", info.in_mappings), ("out", info.out_mappings)):
        for i, bm in enumerate(bms):
            shape, block, _ = _block_dims(bm)
            for d, (s, b) in enumerate(zip(shape, block)):
                if b <= 0 or s % b:
                    out.append(Violation(
                        "block-shape-indivisible",
                        f"{role}[{i}] dim {d}: array {s} not a multiple of "
                        f"block {b} — the ops wrapper must pad before the "
                        "pallas_call",
                        where,
                    ))
    return out


def _index_map_fn(bm, grid_len: int):
    """The index map as a callable of the grid indices, or ``None`` if
    it reads its non-grid operands (scalar prefetch) — not static."""
    imj = bm.index_map_jaxpr            # ClosedJaxpr
    invars = imj.jaxpr.invars
    extra = invars[grid_len:]
    if extra:
        used = set()
        for eqn, _ctx in iter_eqns(imj):
            for v in eqn.invars:
                if not isinstance(v, Literal):
                    used.add(v)
        if any(v in used for v in extra):
            return None
        dummies = [
            np.zeros(getattr(v.aval, "shape", ()), dtype=np.int32)
            for v in extra
        ]
    else:
        dummies = []

    def fn(*idxs):
        return jax.core.eval_jaxpr(
            imj.jaxpr, imj.consts, *idxs, *dummies
        )

    return fn


def _grid_points(grid: tuple) -> np.ndarray:
    """(prod(grid), len(grid)) int32 array of every grid index tuple."""
    if not grid:
        return np.zeros((1, 0), np.int32)
    mesh = np.meshgrid(*[np.arange(g, dtype=np.int32) for g in grid],
                       indexing="ij")
    return np.stack([m.reshape(-1) for m in mesh], axis=-1)


def _eval_index_map(bm, grid: tuple):
    """Evaluate one block's index map over the whole grid.

    Returns ``(points, block_indices)`` — both ``(P, ·)`` int arrays —
    or ``None`` when the map is not static in the grid indices.
    """
    fn = _index_map_fn(bm, len(grid))
    if fn is None:
        return None
    pts = _grid_points(grid)
    if len(grid) == 0:
        outs = [np.asarray(o).reshape(1) for o in fn()]
        return pts, np.stack(outs, axis=-1)
    cols = [jax.numpy.asarray(pts[:, d]) for d in range(len(grid))]
    outs = jax.vmap(lambda *i: tuple(fn(*i)))(*cols)
    idx = np.stack([np.asarray(o) for o in outs], axis=-1)
    return pts, idx


def check_index_maps(info: KernelCallInfo, where: str):
    """Every index map lands in bounds for every grid point."""
    out = []
    for role, bms in (("in", info.in_mappings), ("out", info.out_mappings)):
        for i, bm in enumerate(bms):
            shape, block, _ = _block_dims(bm)
            ev = _eval_index_map(bm, info.grid)
            if ev is None:
                out.append(Violation(
                    "index-map-not-static",
                    f"{role}[{i}] index map reads a non-grid operand "
                    "(scalar prefetch) — cannot be bounds-checked "
                    "statically",
                    where,
                ))
                continue
            pts, idx = ev
            if idx.shape[-1] != len(shape):
                out.append(Violation(
                    "kernel-contract-mismatch",
                    f"{role}[{i}] index map yields {idx.shape[-1]} "
                    f"coordinates for a rank-{len(shape)} operand",
                    where,
                ))
                continue
            nblocks = [max(s // b, 1) for s, b in zip(shape, block)]
            for d, nb in enumerate(nblocks):
                col = idx[:, d]
                bad = np.where((col < 0) | (col >= nb))[0]
                if bad.size:
                    p = tuple(int(x) for x in pts[bad[0]])
                    out.append(Violation(
                        "index-map-out-of-bounds",
                        f"{role}[{i}] dim {d}: grid point {p} maps to "
                        f"block {int(col[bad[0]])}, valid range "
                        f"[0, {nb - 1}] ({bad.size} offending points)",
                        where,
                    ))
                    break
    return out


def check_write_disjointness(
    info: KernelCallInfo, contract: dict, where: str
):
    """Two grid points may write the same output block only if they
    differ solely in the contract's declared reduction axes."""
    out = []
    red = set(contract.get("reduction_axes", ()))
    par = [d for d in range(len(info.grid)) if d not in red]
    for i, bm in enumerate(info.out_mappings):
        ev = _eval_index_map(bm, info.grid)
        if ev is None:
            continue  # flagged by check_index_maps
        pts, idx = ev
        seen: dict = {}
        for p, ix in zip(pts, idx):
            key = tuple(int(x) for x in ix)
            pkey = tuple(int(p[d]) for d in par)
            prev = seen.setdefault(key, pkey)
            if prev != pkey:
                out.append(Violation(
                    "output-overlap-undeclared",
                    f"out[{i}]: grid points {prev} and {pkey} (projected "
                    f"onto non-reduction axes {tuple(par)}) both write "
                    f"block {key} — overlap not covered by declared "
                    f"reduction axes {tuple(sorted(red))}",
                    where,
                ))
                break
    return out


def _body_eqns(info: KernelCallInfo):
    yield from iter_eqns(to_closed_jaxpr(info.body))


def _used_vars(info: KernelCallInfo) -> set:
    used = set()
    for eqn, _ctx in _body_eqns(info):
        for v in eqn.invars:
            if not isinstance(v, Literal):
                used.add(v)
    for jx in _all_jaxprs(info.body):
        for v in jx.outvars:
            if not isinstance(v, Literal):
                used.add(v)
    return used


def _all_jaxprs(jaxpr):
    from repro.analysis.traversal import sub_jaxprs

    stack = [jaxpr]
    while stack:
        j = stack.pop()
        yield j
        for eqn in j.eqns:
            stack.extend(sub_jaxprs(eqn.params))


def _literal_comparisons(info: KernelCallInfo):
    """Yield ``(eqn, literal_value)`` for comparison eqns against an
    integer literal inside the kernel body."""
    for eqn, _ctx in _body_eqns(info):
        if str(eqn.primitive) not in _CMP_PRIMS:
            continue
        for v in eqn.invars:
            if isinstance(v, Literal) and np.ndim(v.val) == 0:
                try:
                    yield eqn, int(v.val)
                except (TypeError, ValueError):
                    pass


def check_masked_tails(
    info: KernelCallInfo, contract: dict, guards: dict, where: str
):
    """Declared masked axes must be guarded by live comparisons.

    ``guards`` comes from the kernel case: ``{axis_name: bound}`` with
    an int bound for a literal guard (ragged kv_len) or the string
    ``"scalar_prefetch"`` for offset-table masking. Axes declared
    masked in the contract but absent from ``guards`` are skipped (the
    case traced a divisible shape — nothing to guard).
    """
    out = []
    used = None
    for axis, bound in guards.items():
        if axis not in contract.get("masked", {}):
            out.append(Violation(
                "kernel-contract-mismatch",
                f"case declares a guard for axis {axis!r} but the "
                "contract lists it unmasked",
                where,
            ))
            continue
        if bound == "scalar_prefetch":
            if info.num_index < 1:
                out.append(Violation(
                    "masked-tail-guard-missing",
                    f"axis {axis!r}: contract masks via scalar prefetch "
                    "but the call carries no scalar-prefetch operand",
                    where,
                ))
                continue
            if used is None:
                used = _used_vars(info)
            pref = info.body.invars[: info.num_index]
            cmps = [e for e, _v in _body_eqns(info)
                    if str(e.primitive) in _CMP_PRIMS]
            if not any(v in used for v in pref) or not cmps:
                out.append(Violation(
                    "masked-tail-guard-missing",
                    f"axis {axis!r}: kernel never reads the prefetched "
                    "offsets / never compares row indices against them",
                    where,
                ))
            continue
        bound = int(bound)
        hits = [eqn for eqn, val in _literal_comparisons(info)
                if val == bound]
        if not hits:
            out.append(Violation(
                "masked-tail-guard-missing",
                f"axis {axis!r}: no comparison against the ragged bound "
                f"{bound} in the kernel body — tail positions leak into "
                "the result",
                where,
            ))
            continue
        if used is None:
            used = _used_vars(info)
        if not any(
            any(ov in used for ov in eqn.outvars) for eqn in hits
        ):
            out.append(Violation(
                "masked-tail-guard-dead",
                f"axis {axis!r}: the comparison against {bound} exists "
                "but its result is never consumed — the guard is dead "
                "code",
                where,
            ))
    return out


def check_acc_dtype(info: KernelCallInfo, contract: dict, where: str):
    """Scratch accumulators carry the contract dtype; low-precision
    operands are widened to fp32 before arithmetic."""
    out = []
    want = np.dtype(contract.get("acc_dtype", "float32"))
    for i, aval in enumerate(info.scratch_avals()):
        got = np.dtype(aval.dtype)
        if got != want:
            out.append(Violation(
                "acc-dtype-not-fp32",
                f"scratch[{i}] accumulator is {got}, contract requires "
                f"{want}",
                where,
            ))
    low = [np.dtype(_block_dims(bm)[2]) for bm in info.in_mappings]
    has_low = any(dt in (np.dtype("bfloat16"), np.dtype("float16"))
                  for dt in low)
    if has_low:
        widens = any(
            str(eqn.primitive) == "convert_element_type"
            and np.dtype(eqn.params.get("new_dtype")) == np.dtype("float32")
            for eqn, _ctx in _body_eqns(info)
        )
        f32_scratch = any(
            np.dtype(a.dtype) == np.dtype("float32")
            for a in info.scratch_avals()
        )
        if not widens and not f32_scratch:
            out.append(Violation(
                "acc-dtype-not-fp32",
                "low-precision operands but no fp32 widening and no fp32 "
                "scratch in the kernel body — accumulation runs in "
                f"{[str(d) for d in low]}",
                where,
            ))
    return out


def vmem_footprint_bytes(info: KernelCallInfo) -> int:
    """Per-grid-step VMEM model: double-buffered in/out blocks (Pallas
    pipelines the next block's DMA against the current compute) plus
    scratch, which is single-buffered and lives across steps."""
    blocks = 0
    for bm in itertools.chain(info.in_mappings, info.out_mappings):
        _, block, dtype = _block_dims(bm)
        blocks += int(np.prod(block)) * np.dtype(dtype).itemsize
    scratch = sum(
        int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
        for a in info.scratch_avals()
    )
    return 2 * blocks + scratch


def check_vmem(info: KernelCallInfo, contract: dict, where: str):
    out = []
    got = vmem_footprint_bytes(info)
    limit = int(contract.get("vmem_limit_bytes", VMEM_BYTES))
    if got > limit:
        out.append(Violation(
            "vmem-bound-exceeded",
            f"modeled per-step footprint {got} B exceeds the contract "
            f"budget {limit} B",
            where,
        ))
    if got > VMEM_BYTES:
        out.append(Violation(
            "vmem-bound-exceeded",
            f"modeled per-step footprint {got} B exceeds the 16 MiB "
            "hardware VMEM",
            where,
        ))
    return out


# ---------------------------------------------------------------------------
# one kernel case end to end
# ---------------------------------------------------------------------------
def lint_pallas_eqn(
    info: KernelCallInfo, contract: dict, guards: dict, where: str
) -> list:
    out = check_contract_shape(info, contract, where)
    out += check_block_divisibility(info, where)
    out += check_index_maps(info, where)
    out += check_write_disjointness(info, contract, where)
    out += check_masked_tails(info, contract, guards, where)
    out += check_acc_dtype(info, contract, where)
    out += check_vmem(info, contract, where)
    return out


def lint_case(case) -> tuple:
    """Trace one :class:`repro.analysis.kernel_cases.KernelCase` and
    lint every pallas_call it reaches. Returns ``(violations, stats)``
    where stats is a JSON-able summary per traced call."""
    closed = jax.make_jaxpr(case.fn)(*case.args)
    infos = find_pallas_calls(closed)
    where = case.label
    if not infos:
        return (
            [Violation(
                "pallas-call-missing",
                "case traced no pallas_call — the wrapper fell back to "
                "a reference path",
                where,
            )],
            [],
        )
    viols, stats = [], []
    for info in infos:
        viols.extend(
            lint_pallas_eqn(info, case.contract, case.guards, where)
        )
        stats.append({
            "grid": list(info.grid),
            "num_inputs": info.num_inputs,
            "num_outputs": info.num_outputs,
            "num_scratch": info.num_scratch,
            "vmem_footprint_bytes": vmem_footprint_bytes(info),
            "vmem_limit_bytes": int(case.contract["vmem_limit_bytes"]),
        })
    return viols, stats


# ---------------------------------------------------------------------------
# source lint: hardcoded interpret= outside ops.py
# ---------------------------------------------------------------------------
def check_interpret_literals(root: str | None = None) -> list:
    """AST-walk ``src/repro`` for a literal ``interpret=True/False``
    call argument anywhere but ``kernels/ops.py``. The resolution
    lives in ``ops.resolve_mode``; a hardcoded literal elsewhere pins
    a kernel to one backend behind the dispatcher's back."""
    import repro

    if root is None:
        root = os.path.dirname(os.path.abspath(repro.__file__))
    allowed = os.path.join(root, "kernels", "ops.py")
    out = []
    for dirpath, _dirs, files in sorted(os.walk(root)):
        for fname in sorted(files):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            if os.path.abspath(path) == os.path.abspath(allowed):
                continue
            with open(path) as f:
                try:
                    tree = ast.parse(f.read(), filename=path)
                except SyntaxError:
                    continue
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                for kw in node.keywords:
                    if (
                        kw.arg == "interpret"
                        and isinstance(kw.value, ast.Constant)
                        and isinstance(kw.value.value, bool)
                    ):
                        rel = os.path.relpath(path, root)
                        out.append(Violation(
                            "hardcoded-interpret-mode",
                            f"interpret={kw.value.value} hardcoded at "
                            f"{rel}:{node.lineno} — route through "
                            "kernels.ops.resolve_mode instead",
                            f"src/{rel}",
                        ))
    return out
