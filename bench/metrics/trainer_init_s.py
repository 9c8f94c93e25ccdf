"""Host seconds of the program's `Trainer` construction in set-up (its
`trainer_init` span): checkpointer, data, parallelize, the train step's
jit and the cost model."""

import scopes


def read(ctx):
    return scopes.span_s(ctx, "trainer_init")
