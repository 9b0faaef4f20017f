"""Model FLOPs of the traced window's tokens (``bench/flops``, no
recomputation) over window x chips x the chips' bf16 peak, in %."""


def read(ctx):
    t0, t1 = ctx.window
    if ctx.tokens <= 0 or t1 <= t0:
        return None
    done = ctx.flops_per_token * ctx.tokens
    return 100.0 * done / ((t1 - t0) * 1e-9 * ctx.chips
                           * ctx.peaks["bf16_flops"])
