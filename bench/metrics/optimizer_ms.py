"""Device time per step of the ops under scope ``optimizer``, on the
chip where it is longest."""
from bench.trace import in_scope


def read(ctx):
    ms = ctx.scope_ms(lambda op: in_scope(op.scope, "optimizer"))
    return ms or None
