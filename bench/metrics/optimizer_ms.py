"""Device time of the optimizer step: gradient norm, clip and the AdamW
update (the program's `optimizer` scope), ms per step, the mean over the
cell's devices (profiler trace)."""

import scopes


def read(ctx):
    return scopes.device_ms(ctx, "optimizer")
