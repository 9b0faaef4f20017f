"""Device time per step of the ops under scope ``fwd_bwd``, on the chip
where it is longest."""
from bench.trace import in_scope


def read(ctx):
    ms = ctx.scope_ms(lambda op: in_scope(op.scope, "fwd_bwd"))
    return ms or None
