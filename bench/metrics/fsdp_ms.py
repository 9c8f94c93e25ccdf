"""Device time of SimpleFSDP's collectives with their copies: the
parameter buckets' pack, all-gather and unpack, and the gradient buckets'
copy-in, reduce-scatter and split (the program's `fsdp_gather` and
`fsdp_reduce_scatter` scopes), ms per step, the mean over the cell's
devices (profiler trace). On size-1 FSDP axes only the copies and casts run."""

import scopes


def read(ctx):
    return scopes.device_ms(ctx, "fsdp_gather", "fsdp_reduce_scatter")
