"""Device time of the final norm, the LM head and the cross-entropy, with
their backward (the program's `head_loss` scope), ms per step, the mean
over the cell's devices (profiler trace)."""

import scopes


def read(ctx):
    return scopes.device_ms(ctx, "head_loss")
