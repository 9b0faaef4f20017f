"""Per step, on the chip where it is longest: the time of collectives
under ``gossip/matching*`` during which no other op runs on that chip."""
from bench.trace import clip, exclusive_ns, in_scope, is_collective


def _gossip_collective(op):
    return is_collective(op.name) and in_scope(op.scope, "gossip/matching*")


def read(ctx):
    t0, t1 = ctx.window
    per_chip = [clip(ops, t0, t1) for ops in ctx.device_ops()]
    if not any(_gossip_collective(o) for ops in per_chip for o in ops):
        return None
    worst = max(exclusive_ns(ops, _gossip_collective) for ops in per_chip)
    return worst / ctx.steps / 1e6
