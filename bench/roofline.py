"""A Pallas kernel's share of its memory roofline: the bytes its calls read
and write (operand and result shapes in the compiled step) at the chip's
HBM bandwidth, over the device time of those calls in the trace; the mean
over the cell's devices. None where the step has no such call."""


def roofline(ctx, kernel: str):
    calls = {n: b for n, (k, b) in ctx.kernels.items() if k == kernel}
    if not calls:
        return None
    shares = []
    for d in ctx.devices:
        ops = [o for o in ctx.trace.ops[d] if o.name in calls
               and ctx.lo <= o.start < ctx.hi]
        secs = sum(o.end - o.start for o in ops) / 1e9
        if not ops or secs <= 0:
            return None
        nbytes = sum(calls[o.name] for o in ops)
        shares.append(nbytes / ctx.peaks["hbm_bytes_per_s"] / secs)
    return 100.0 * sum(shares) / len(shares)
