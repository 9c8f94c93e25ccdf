"""Device time of the attention branches with their pre-norms, forward,
recompute and backward (the program's `attn` scope), ms per step, the mean
over the cell's devices (profiler trace)."""

import scopes


def read(ctx):
    return scopes.device_ms(ctx, "attn")
