"""Device time per step of the ops under scope ``fwd_bwd`` that are
also under scope ``ssd``, the Mamba2 block's SSD scan (``ssd_chunked``
or ``ssd_sequential``), on the chip where it is longest."""
from bench.trace import in_scope


def read(ctx):
    ms = ctx.scope_ms(lambda op: in_scope(op.scope, "fwd_bwd")
                      and in_scope(op.scope, "ssd"))
    return ms or None
