"""Share of the traced window in which the busiest chip runs no op:
1 - (union of its op intervals) / window."""
from bench.trace import clip, union_ns


def read(ctx):
    t0, t1 = ctx.window
    busy = [union_ns(clip(ops, t0, t1)) for ops in ctx.device_ops()]
    if not busy or max(busy) <= 0:
        return None
    return 1.0 - max(busy) / (t1 - t0)
