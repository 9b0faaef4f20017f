"""Share of the HBM roofline that the consensus update reaches, in %.

The update x <- x + alpha sum_j b_j (x_j - x) needs, on a step with k
activated matchings (k >= 1), one read of x, one of each of the k
partner copies and one write: 4 B x replica elements x (2 + k). A step
with none needs nothing. That is the algorithm's work, whatever does
it. Its time: device time of the non-collective ops under ``gossip`` on
the chip where that is longest."""
from bench.trace import in_scope, is_collective


def needed_bytes(replica_elements: int, bits_rows) -> float:
    total = 0.0
    for row in bits_rows:
        k = int(round(sum(row)))
        if k:
            total += 4.0 * replica_elements * (2 + k)
    return total


def read(ctx):
    need = needed_bytes(ctx.replica_elements, ctx.bits_rows)
    ms = ctx.scope_ms(lambda op: in_scope(op.scope, "gossip")
                      and not is_collective(op.name))
    if need <= 0 or not ms:
        return None
    seconds = ms * ctx.steps * 1e-3
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / seconds
