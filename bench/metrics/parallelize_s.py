"""Host seconds of `parallelize` in set-up (the program's `parallelize`
span): the planners that resolve the ParallelPlan."""

import scopes


def read(ctx):
    return scopes.span_s(ctx, "parallelize")
