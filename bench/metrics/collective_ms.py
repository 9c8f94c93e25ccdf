"""Device time of the cross-chip collectives, ms per step, the mean over the
cell's devices (profiler trace): the union of the collectives' own events,
a synchronous op's and an async pair's start and done; the time from a
start to its done is logged apart (bench/collectives.py). None on one
chip."""

import collectives


def read(ctx):
    times = collectives.device_times(ctx)
    if not times:
        return None
    return sum(t for t, _ in times.values()) / len(times) / 1e6 / ctx.steps
