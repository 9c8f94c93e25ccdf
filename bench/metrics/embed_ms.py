"""Device time of the token embedding, the lookup and its backward
scatter-add (the program's `embed` scope), ms per step, the mean over the
cell's devices (profiler trace; ops mapped by the compiled step's op_name)."""

import scopes


def read(ctx):
    return scopes.device_ms(ctx, "embed")
