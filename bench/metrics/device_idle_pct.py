"""Share of the traced window in which no operation runs on a device, the
mean over the cell's devices (profiler trace)."""

import tracelib


def read(ctx):
    win = ctx.hi - ctx.lo
    busy = [tracelib.length(tracelib.busy(ctx.trace, d, ctx.lo, ctx.hi))
            for d in ctx.devices]
    return 100.0 * (1.0 - sum(busy) / len(busy) / win)
