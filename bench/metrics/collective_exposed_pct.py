"""Share of `collective_ms` in which no other op runs on the chip, the mean
over the cell's devices (profiler trace, bench/collectives.py): the
collective time that prefetch and the delayed reduce-scatters leave in the
step's critical path. None on one chip."""

import collectives


def read(ctx):
    times = collectives.device_times(ctx)
    if not times:
        return None
    return 100.0 * sum(e / t for t, e in times.values()) / len(times)
