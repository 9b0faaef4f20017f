"""Read a profiler trace (``.xplane.pb``) and reduce it to intervals.

``load`` gives, per device, its operations as :class:`Op` (start and
end in ns on the trace's clock, the HLO op and module names, and the
name-scope path), and the host spans that the harness wrote with
``jax.profiler.TraceAnnotation``. The rest are plain functions over
those lists, which the per-layer metrics in ``bench/metrics`` call.

The scope of an op is its ``tf_op`` / ``long_name`` stat where the
trace carries one, else the ``op_name`` metadata of the instruction of
that name in the compiled module's HLO text (``scope_map``). A TPU trace
names each op by its HLO text and carries no scope.
"""
from __future__ import annotations

import dataclasses
import re
from collections import Counter, defaultdict
from typing import Dict, List, Optional

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?$")
_OPNAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
# a TPU trace names each op by its HLO text: "%fusion.15 = (...) fusion(...)"
_EVENT = re.compile(r"^%?([\w.\-]+)\s*=")


@dataclasses.dataclass(frozen=True)
class Op:
    device: int
    start: float            # ns
    end: float              # ns
    name: str               # HLO op name
    module: str
    scope: str              # name-scope path ("" where unknown)


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Op]]                   # device -> ops by start
    spans: List[tuple]                         # (name, start_ns, end_ns)

    def window(self):
        """From the first device op to the last. The trace holds only
        the traced steps (the harness waits for the device before and
        after), and the device clock need not agree with the host's to
        the millisecond, so the host's span does not bound it."""
        ops = [o for dev in self.ops.values() for o in dev]
        if not ops:
            raise ValueError("the trace holds no device op")
        return min(o.start for o in ops), max(o.end for o in ops)


def module_name(hlo_text: str) -> str:
    for line in hlo_text.splitlines():
        m = _MODULE.match(line)
        if m:
            return m.group(1)
    return ""


def scope_map(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> ``op_name`` metadata. An instruction that
    calls a computation (a fusion) and whose own scope is missing or
    encloses its ops' takes the most common ``op_name`` of those ops,
    counting only the most specific scopes: XLA fuses the optimizer's
    update into the fusion that stacks the node dim, whose own metadata
    names the stacking (``jit(body)/broadcast_in_dim``), not the update."""
    direct: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    per_comp: Dict[str, Counter] = defaultdict(Counter)
    comp = ""
    for line in hlo_text.splitlines():
        c = _COMP.match(line)
        if c and "=" not in line.split("{")[0]:
            comp = c.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OPNAME.search(line)
        if op:
            direct[name] = op.group(1)
            per_comp[comp][op.group(1)] += 1
        call = _CALLS.search(line)
        if call:
            calls[name] = call.group(1)
    out = dict(direct)
    for name, callee in calls.items():
        if not per_comp.get(callee):
            continue
        inner = _most_specific(per_comp[callee])
        own = direct.get(name)
        if own is None or inner.startswith(own.rsplit("/", 1)[0] + "/"):
            out[name] = inner
    return out


def _most_specific(op_names: Counter) -> str:
    """The most common op name, among those whose scope path is no
    prefix of another's."""
    scopes = {n: n.rsplit("/", 1)[0] for n in op_names}
    keep = [n for n, s in scopes.items()
            if not any(o != s and o.startswith(s + "/")
                       for o in scopes.values())]
    return max(keep, key=lambda n: op_names[n])


def in_scope(path: str, scope: str) -> bool:
    """Whether ``scope`` (e.g. ``fwd_bwd`` or ``gossip/matching``) is on
    the name-scope path, also under transforms (``transpose(jvp(x))``).
    A trailing ``*`` matches any suffix of the last element."""
    parts = [p for p in re.split(r"[/()]", path) if p]
    want = scope.split("/")
    for i in range(len(parts) - len(want) + 1):
        ok = True
        for j, w in enumerate(want):
            got = parts[i + j]
            if w.endswith("*"):
                ok = got.startswith(w[:-1])
            else:
                ok = got == w
            if not ok:
                break
        if ok:
            return True
    return False


def is_collective(name: str) -> bool:
    return bool(re.match(
        r"(collective-permute|all-reduce|all-gather|all-to-all|"
        r"reduce-scatter|send|recv)", name))


def _stat(stats: dict, *keys) -> Optional[str]:
    for k in keys:
        v = stats.get(k)
        if isinstance(v, bytes):
            v = v.decode()
        if v:
            return str(v)
    return None


def _device_index(plane_name: str) -> Optional[int]:
    m = re.match(r"/device:[A-Z]+:(\d+)", plane_name)
    return int(m.group(1)) if m else None


def load(path: str, scopes: Optional[Dict[str, Dict[str, str]]] = None
         ) -> Trace:
    """Read ``path``. ``scopes`` maps module name -> :func:`scope_map`
    of that module, for ops whose trace carries no scope."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    scopes = scopes or {}
    ops: Dict[int, List[Op]] = defaultdict(list)
    spans = []
    for plane in data.planes:
        dev = _device_index(plane.name)
        if dev is None:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith("bench/"):
                            spans.append((ev.name, ev.start_ns, ev.end_ns))
            continue
        lines = {line.name: line for line in plane.lines}
        op_line = lines.get("XLA Ops")
        if op_line is None:
            continue
        modules = []
        mod_line = lines.get("XLA Modules")
        if mod_line is not None:
            modules = [(ev.start_ns, ev.end_ns, ev.name)
                       for ev in mod_line.events]
        for ev in op_line.events:
            stats = dict(ev.stats)
            m = _EVENT.match(ev.name)
            name = _stat(stats, "hlo_op") or (m.group(1) if m else ev.name)
            module = _stat(stats, "hlo_module") or _enclosing(
                modules, ev.start_ns)
            scope = _stat(stats, "tf_op", "long_name", "name_scope") or ""
            if "/" not in scope:
                table = _table_for(scopes, module)
                scope = table.get(name, scope)
            ops[dev].append(Op(dev, ev.start_ns, ev.end_ns, name, module,
                               scope))
    for dev in ops:
        ops[dev].sort(key=lambda o: (o.start, -o.end))
    spans.sort(key=lambda s: s[1])
    return Trace(ops=dict(ops), spans=spans)


def _enclosing(modules, t) -> str:
    for s, e, name in modules:
        if s <= t <= e:
            return name
    return ""


def _table_for(scopes, module: str) -> Dict[str, str]:
    if module in scopes:
        return scopes[module]
    for key, table in scopes.items():
        # trace module names carry a suffix such as "(123)"
        if module.startswith(key):
            return table
    return {}


def clip(ops: List[Op], t0: float, t1: float) -> List[Op]:
    out = []
    for o in ops:
        s, e = max(o.start, t0), min(o.end, t1)
        if e > s:
            out.append(dataclasses.replace(o, start=s, end=e))
    return out


def union_ns(ops: List[Op]) -> float:
    """Length of the union of the ops' intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for o in sorted(ops, key=lambda o: o.start):
        if cur_e is None or o.start > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = o.start, o.end
        else:
            cur_e = max(cur_e, o.end)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(ops: List[Op], t0: float, t1: float) -> List[tuple]:
    """Idle intervals (start, end) of the union within [t0, t1]."""
    out, cur = [], t0
    for o in sorted(ops, key=lambda o: o.start):
        if o.start > cur:
            out.append((cur, min(o.start, t1)))
        cur = max(cur, o.end)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1))
    return [(s, e) for s, e in out if e > s]


def segments(ops: List[Op]):
    """Split the timeline at every op boundary: yields (start, end,
    active ops) for each piece in which some op runs."""
    bounds = sorted({o.start for o in ops} | {o.end for o in ops})
    starts = sorted(ops, key=lambda o: o.start)
    active: List[Op] = []
    i = 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(starts) and starts[i].start <= a:
            active.append(starts[i])
            i += 1
        active = [o for o in active if o.end > a]
        if active:
            yield a, b, active


def self_time_ns(ops: List[Op], pred) -> float:
    """Time in which the innermost running op (latest start) satisfies
    ``pred``: nested ops (a loop and its body) are counted once."""
    total = 0.0
    for a, b, active in segments(ops):
        inner = max(active, key=lambda o: (o.start, -o.end))
        if pred(inner):
            total += b - a
    return total


def exclusive_ns(ops: List[Op], pred) -> float:
    """Time in which ops satisfying ``pred`` run and no other op does.
    An op that encloses every such running op (a loop around it) is
    its container, not another op."""
    total = 0.0
    for a, b, active in segments(ops):
        hits = [o for o in active if pred(o)]
        if not hits:
            continue
        others = [o for o in active if not pred(o)]
        if all(o.start <= h.start and o.end >= h.end
               for o in others for h in hits):
            total += b - a
    return total
