"""Model FLOP/s utilization of the whole step: the forward and backward
FLOPs the traced steps' tokens need (recomputation not counted) over the
traced window, as a share of the cell's chips' bf16 peak."""


def read(ctx):
    secs = (ctx.hi - ctx.lo) / 1e9
    flops = ctx.flops_per_token * ctx.tokens_per_step * ctx.steps
    return 100.0 * flops / secs / (ctx.chips * ctx.peaks["bf16_flops_per_s"])
