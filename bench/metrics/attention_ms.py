"""Device time per step of the ops under scope ``fwd_bwd`` that are
also under scope ``attention``, the self-attention block (q/k/v
projections, rope, scores, out projection), on the chip where it is
longest."""
from bench.trace import in_scope


def read(ctx):
    ms = ctx.scope_ms(lambda op: in_scope(op.scope, "fwd_bwd")
                      and in_scope(op.scope, "attention"))
    return ms or None
