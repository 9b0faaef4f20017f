"""Device time per step of the ops under scope ``fwd_bwd`` that are
also under scope ``ffn``, the feed-forward block (``ffn_block`` or
``moe_block``), on the chip where it is longest."""
from bench.trace import in_scope


def read(ctx):
    ms = ctx.scope_ms(lambda op: in_scope(op.scope, "fwd_bwd")
                      and in_scope(op.scope, "ffn"))
    return ms or None
