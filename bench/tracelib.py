"""The reduction from a profiler trace to intervals: device operations per
chip and the harness's own host spans, on one clock.

`load` reads the `.xplane.pb` that `jax.profiler` writes; the functions
below it are plain interval arithmetic over what it returns, so a metric
reader is a few lines and the tests can feed them hand-made events.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

# HLO ops that move data between chips (an async pair counts from its
# start op to its done op on the device that waits)
COLLECTIVE = re.compile(
    r"^(all-gather|reduce-scatter|all-reduce|collective-permute|all-to-all)"
    r"|^(all_gather|reduce_scatter|all_reduce)")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# a TPU trace names each op by its whole HLO text: "%fusion.3 = f32[8]{0}
# fusion(...), kind=kLoop, ..."; the instruction's name is its first word
HLO_NAME = re.compile(r"\s*%?([^\s=]+)")
HLO_HEAD = re.compile(r"\s*%?([^\s=]+) = (.*?) ([\w\-]+)\(")
LAYOUT = re.compile(r"\{[^{}]*\}")
HOST_SPANS = ("batch", "dispatch", "sync")


@dataclasses.dataclass(frozen=True)
class Op:
    start: float          # ns, on the host's clock
    end: float
    name: str             # the HLO instruction name
    category: str = ""    # the profiler's hlo_category, where it gives one
    text: str = ""        # the instruction's HLO text, where the trace has it


@dataclasses.dataclass
class Trace:
    ops: dict[int, list[Op]]                       # device id -> ops
    spans: list[tuple[float, float, str]]          # host spans, ns


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def load(trace_dir: str) -> Trace:
    """The newest trace under `trace_dir`: every op of each TPU device's
    XLA op line, and the harness's host spans (`HOST_SPANS`)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    ops: dict[int, list[Op]] = {}
    spans = []
    for plane in data.planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev and line.name == "XLA Ops":
                ops[int(dev.group(1))] = sorted(
                    (Op(e.start_ns, e.end_ns, HLO_NAME.match(e.name).group(1),
                        str(_stat(e, "hlo_category") or ""), e.name)
                     for e in line.events), key=lambda o: o.start)
            elif not dev:
                spans.extend((e.start_ns, e.end_ns, e.name)
                             for e in line.events if e.name in HOST_SPANS)
    return Trace(ops, sorted(spans))


def is_collective(op: Op) -> bool:
    return bool(COLLECTIVE.match(op.name)) or \
        bool(COLLECTIVE.match(op.category.replace(" ", "-")))


def merge(intervals) -> list[tuple[float, float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def minus(a, b) -> list[tuple[float, float]]:
    """The parts of merged intervals `a` that merged intervals `b` leave
    uncovered."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def window(trace: Trace) -> tuple[float, float]:
    """From the first host span to the last: the traced steps' first batch
    to their last sync. Where the device ops lie outside it (a trace whose
    device clock is not the host's), the device ops' own extent."""
    ops = [o for v in trace.ops.values() for o in v]
    if trace.spans:
        lo, hi = trace.spans[0][0], max(e for _, e, _ in trace.spans)
        if any(lo <= o.start < hi for o in ops):
            return lo, hi
    if not ops:
        raise ValueError("the trace holds no device op")
    return min(o.start for o in ops), max(o.end for o in ops)


def busy(trace: Trace, dev: int, lo: float, hi: float):
    return clip(merge((o.start, o.end) for o in trace.ops[dev]), lo, hi)


def collective_exposed(trace: Trace, dev: int, lo: float, hi: float):
    """Where a collective runs on `dev` and no other op does."""
    coll = merge((o.start, o.end) for o in trace.ops[dev] if is_collective(o))
    other = merge((o.start, o.end) for o in trace.ops[dev]
                  if not is_collective(o))
    return minus(clip(coll, lo, hi), other)


def idle_gaps(trace: Trace, dev: int, lo: float, hi: float):
    """The device's idle intervals in the window, each with the host span
    that covers its midpoint ('none' where the host was in none)."""
    gaps = minus([(lo, hi)], busy(trace, dev, lo, hi))
    out = []
    for s, e in gaps:
        mid = (s + e) / 2
        label = next((n for a, b, n in trace.spans if a <= mid < b), "none")
        out.append((label, e - s))
    return out


def self_times(ops, lo: float, hi: float) -> dict[str, float]:
    """Device time in [lo, hi) per op name, less the time of the ops nested
    inside it (a loop's body ops run inside the loop's own event)."""
    total: dict[str, float] = {}
    stack: list[tuple[float, str]] = []          # open ops: (end, name)
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and stack[-1][0] <= o.start:
            stack.pop()
        d = max(0.0, min(o.end, hi) - max(o.start, lo))
        total[o.name] = total.get(o.name, 0.0) + d
        if stack and o.end <= stack[-1][0]:
            total[stack[-1][1]] -= d
        stack.append((o.end, o.name))
    return total


def label(op: Op) -> str:
    """A short label for a device op: its name, opcode and result type,
    without layouts."""
    text = LAYOUT.sub("", op.text or op.name)
    m = HLO_HEAD.match(text)
    return (f"{m.group(1)} {m.group(3)} {m.group(2)}" if m else text)[:160]
