"""Bytes each chip's collectives exchange a step, GiB, from the compiled
step's text (bench/collectives.py): an all-gather's result, a
reduce-scatter's or an all-reduce's operand, times its loop's trips. The
log line gives each kind apart, and the plan's own count beside it (the
Trainer's `train/wire_bytes` numbers). None on one chip."""

import collectives


def read(ctx):
    if getattr(ctx, "chips", 1) < 2:
        return None
    text = collectives.hlo(ctx)
    if not text:
        return None
    ex = collectives.exchanged(text)
    total = sum(ex[k][1] for k in collectives.KINDS)
    if not total:
        return None
    parts = ", ".join(f"{k} {ex[k][0]} calls {ex[k][1] / 2**30:.4f} GiB"
                      for k in collectives.KINDS)
    plan = collectives.planned_bytes(ctx)
    print(f"collective_gib: {parts}; loops counted once: "
          f"{ex['unknown_trips']}; the plan's wire bytes: "
          f"{'none' if plan is None else f'{plan / 2**30:.4f} GiB'}",
          flush=True)
    return total / 2**30
