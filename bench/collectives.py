"""The step's cross-chip collectives: the bytes each chip's collectives
exchange a step, from the compiled step's text, and the device time they
take and leave exposed, from the profiler trace.

The text. A compiled step is the program of one chip. It holds collectives
in three forms: a synchronous op (`all-gather`, `all-reduce`,
`reduce-scatter`), an async pair (`all-gather-start` ... `all-gather-done`),
and the TPU's async collective fusions: a chain of fusions from
`async-collective-start` to `async-collective-done`, each of which carries
a copy of the same collective (the same `chain_id`) beside the compute it
overlaps. A chain is counted once, at its done. A collective in a loop's
body counts once per trip: the trip count is the loop's
`known_trip_count` where the compiler gives it, else the bound of its
condition's `compare(counter, constant)`, direction LT, less the counter's
initial constant. An all-gather counts its result; a reduce-scatter its
operand (the result times the group); an all-reduce its operand, which is
its result. Empty `replica_groups` are one group of every device.

The trace, each chip's `XLA Ops` line (`tracelib.load`). The chip runs one
op at a time; an event that lies inside another is part of it (a loop's
body inside the loop's event). A collective's device time is its own
events: a synchronous op's, and an async pair's or chain's start and done,
where the chip starts the transfer and waits for what is left of it. The
time from a start to its done, in which the transfer may run under other
ops, is logged apart and counted in neither metric: its length is where
the schedule put the done, not what the transfer took. The time a
collective is exposed is the part of its own events in which no other op
runs: an op that lies inside a collective's event is the collective's own,
and a loop, a call or a conditional event contains other ops and counts as
none.

The compiled text comes from the harness's `Run` that made `ctx`
(`scopes._harness_run`).
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re

import tracelib

KINDS = ("all-gather", "reduce-scatter", "all-reduce")
OPCODE = re.compile(r"^(all-gather|reduce-scatter|all-reduce)(-start|-done)?$")
CHAIN_START, CHAIN_DONE = "async-collective-start", "async-collective-done"
CONTAINERS = ("while", "call", "conditional")
COMP_HEAD = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
INST = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")
CALLED = re.compile(r"\b(calls|body|condition|to_apply)=%([\w.\-]+)")
OPERAND = re.compile(r"%([\w.\-]+)")
GROUPS = re.compile(r"replica_groups=(\{\{[\d,]*\}|\[[\d,]+\]<=\[[\d,]+\]|\{\})")
PARTITIONS = re.compile(r"\bnum_partitions=(\d+)")
REPLICAS = re.compile(r"\breplica_count=(\d+)")
CHAIN = re.compile(r'chain_id="(\d+)"')
TRIP = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')


@dataclasses.dataclass(frozen=True)
class Inst:
    name: str
    type: str       # the result type
    opcode: str
    rest: str       # operands and attributes


def parse(text: str) -> tuple[str, dict[str, list[Inst]]]:
    """(entry computation's name, {computation: its instructions})."""
    comps: dict[str, list[Inst]] = {}
    entry, cur = "", None
    for line in text.splitlines():
        head = COMP_HEAD.match(line)
        if head:
            cur = head.group(2)
            comps[cur] = []
            entry = cur if head.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            m = INST.match(line)
            if m:
                comps[cur].append(Inst(*m.groups()))
    return entry, comps


def _operands(inst: Inst) -> list[str]:
    """The instructions `inst` reads (the names before its attributes)."""
    depth, end = 1, len(inst.rest)
    for i, c in enumerate(inst.rest):
        depth += {"(": 1, ")": -1}.get(c, 0)
        if depth == 0:
            end = i
            break
    return OPERAND.findall(inst.rest[:end])


def trip_count(inst: Inst, comps, insts: dict[str, Inst]) -> int | None:
    """A while loop's trip count, None where the text does not give it."""
    known = TRIP.search(inst.rest)
    if known:
        return int(known.group(1))
    cond = dict(CALLED.findall(inst.rest)).get("condition")
    body = {i.name: i for i in comps.get(cond, [])}
    root = next((i for i in comps.get(cond, [])
                 if i.opcode == "compare" and "direction=LT" in i.rest), None)
    if root is None:
        return None
    a, b = (_operands(root) + [None, None])[:2]
    if not (b in body and body[b].opcode == "constant"
            and a in body and body[a].opcode == "get-tuple-element"):
        return None
    bound = int(re.match(r"-?\d+", body[b].rest).group(0))
    index = int(re.search(r"index=(\d+)", body[a].rest).group(1))
    init = insts.get((_operands(inst) or [""])[0])
    if init is None or init.opcode != "tuple":
        return None
    start = insts.get(_operands(init)[index])
    while start is not None and start.opcode in ("copy", "bitcast"):
        start = insts.get((_operands(start) or [""])[0])
    if start is None or start.opcode != "constant":
        return None
    return bound - int(re.match(r"-?\d+", start.rest).group(0))


def multipliers(entry: str, comps) -> tuple[dict[str, int], list[str]]:
    """({computation: times it runs a step}, the loops whose trip count the
    text does not give, counted once)."""
    insts = {i.name: i for body in comps.values() for i in body}
    edges, unknown = [], []
    for c, body in comps.items():
        for inst in body:
            for key, callee in CALLED.findall(inst.rest):
                n = 1
                if inst.opcode == "while" and key == "body":
                    n = trip_count(inst, comps, insts)
                    if n is None:
                        unknown.append(inst.name)
                        n = 1
                edges.append((c, callee, n))
    mult = {entry: 1}
    for _ in range(len(comps)):             # to a fixed point: calls nest
        before = dict(mult)
        for c, callee, n in edges:
            if c in mult:
                mult[callee] = max(mult.get(callee, 0), mult[c] * n)
        if mult == before:
            break
    return mult, unknown


def devices_of(text: str) -> int:
    """The devices the compiled module runs on: partitions × replicas."""
    p, r = PARTITIONS.search(text), REPLICAS.search(text)
    return (int(p.group(1)) if p else 1) * (int(r.group(1)) if r else 1)


def _group_size(inst: Inst, devices: int) -> int:
    g = GROUPS.search(inst.rest)
    if not g:
        return 0
    g = g.group(1)
    if g == "{}":
        return devices
    if g.startswith("{"):
        return len(g.strip("{}").split(","))
    return int(g[1:].split("]")[0].split(",")[-1])


def _callers(comps) -> dict[str, Inst]:
    out = {}
    for body in comps.values():
        for inst in body:
            for _, callee in CALLED.findall(inst.rest):
                out[callee] = inst
    return out


def exchanged(text: str) -> dict:
    """{kind: [calls, bytes]} a step exchanges on each chip, for KINDS over
    groups of more than one chip; and "unknown_trips", the loops counted
    once for want of a trip count."""
    from flops import array_bytes

    entry, comps = parse(text)
    mult, unknown = multipliers(entry, comps)
    callers = _callers(comps)
    devices = devices_of(text)
    types = {i.name: i.type for body in comps.values() for i in body}
    out = {k: [0, 0] for k in KINDS}
    for comp, body in comps.items():
        caller = callers.get(comp)
        for inst in body:
            kind = OPCODE.match(inst.opcode)
            if not kind or kind.group(2) == "-done":
                continue
            group = _group_size(inst, devices)
            if group < 2:
                continue
            # a chain of async collective fusions counts at its done only
            if CHAIN.search(inst.rest) and caller is not None and \
                    not caller.name.startswith(CHAIN_DONE):
                continue
            nbytes = array_bytes(inst.type)
            if kind.group(2) == "-start" and kind.group(1) == "all-gather":
                # its result is the tuple (operands, gathered)
                nbytes -= sum(array_bytes(types.get(o, ""))
                              for o in _operands(inst))
            if kind.group(1) == "reduce-scatter":
                nbytes *= group
            n = mult.get(comp, 0)
            out[kind.group(1)][0] += n
            out[kind.group(1)][1] += n * nbytes
    out["unknown_trips"] = unknown
    return out


# ---------------------------------------------------------------- trace --
@dataclasses.dataclass(frozen=True)
class Roles:
    """The step's collective instructions by name: the synchronous ops over
    more than one chip (`sync`), those that open and close an async pair or
    chain (`starts`, `dones`), and each done's start (`pairs`)."""
    sync: frozenset
    starts: frozenset
    dones: frozenset
    pairs: dict


def roles(text: str) -> Roles:
    """The collective instructions of the compiled text: ops with a
    collective opcode, and the fusions that open and close a chain."""
    _, comps = parse(text)
    devices = devices_of(text)
    insts = {i.name: i for body in comps.values() for i in body}
    kind = {n: OPCODE.match(i.opcode) for n, i in insts.items()}
    sync = {n for n, k in kind.items() if k and not k.group(2)
            and _group_size(insts[n], devices) > 1}
    starts = {n for n, k in kind.items() if k and k.group(2) == "-start"} | \
        {n for n in insts if n.startswith(CHAIN_START)}
    dones = {n for n, k in kind.items() if k and k.group(2) == "-done"} | \
        {n for n in insts if n.startswith(CHAIN_DONE)}
    pairs = {}
    for n in dones:
        seen, todo = set(), _operands(insts[n])
        while todo:                     # back through the chain's steps
            o = todo.pop(0)
            if o in seen or o not in insts:
                continue
            seen.add(o)
            if o in starts:
                pairs[n] = o
                break
            todo.extend(_operands(insts[o]))
    return Roles(frozenset(sync), frozenset(starts), frozenset(dones), pairs)


def _opcode(op) -> str:
    m = tracelib.HLO_HEAD.match(op.text or "")
    return m.group(3) if m else op.name.split(".")[0]


@dataclasses.dataclass
class Split:
    """One device's `XLA Ops` events in the window, by role: intervals
    (ns) of the synchronous collectives (`sync`), of async starts and
    dones (`ends`), of other ops outside any collective (`other`, each
    with its name), each pair's or chain's start to done (`flight`, by
    done), and the ops that lie inside a collective's event (`inside`,
    {(collective, op): ns})."""
    sync: dict = dataclasses.field(
        default_factory=lambda: collections.defaultdict(list))
    ends: list = dataclasses.field(default_factory=list)
    other: list = dataclasses.field(default_factory=list)
    flight: dict = dataclasses.field(
        default_factory=lambda: collections.defaultdict(list))
    inside: collections.Counter = dataclasses.field(
        default_factory=collections.Counter)

    def own(self) -> list:
        """The collectives' own time, merged."""
        return tracelib.merge([iv for v in self.sync.values() for iv in v]
                              + self.ends)

    def others(self) -> list:
        return tracelib.merge((s, e) for s, e, _ in self.other)


def split(ops, r: Roles, lo: float, hi: float) -> Split:
    """One device's ops in [lo, hi), by role (`Split`)."""
    out = Split()
    stack: list[tuple[float, str | None]] = []  # open: (end, collective)
    open_starts = collections.defaultdict(collections.deque)
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and stack[-1][0] <= o.start:
            stack.pop()
        owner = stack[-1][1] if stack and o.end <= stack[-1][0] else None
        s, e = max(o.start, lo), min(o.end, hi)
        mine = o.name in r.sync or o.name in r.starts or o.name in r.dones
        if owner is not None:
            if e > s:
                out.inside[(owner, o.name)] += e - s
        elif mine:
            if o.name in r.starts:
                open_starts[o.name].append(o.start)
            elif open_starts[r.pairs.get(o.name)]:
                out.flight[o.name].append(
                    (open_starts[r.pairs[o.name]].popleft(), o.end))
            if e > s:
                (out.sync[o.name] if o.name in r.sync
                 else out.ends).append((s, e))
        elif e > s and _opcode(o) not in CONTAINERS:
            out.other.append((s, e, o.name))
        stack.append((o.end, o.name if owner is None and mine else owner))
    return out


def beside(sp: Split, name: str, top: int = 8) -> list[tuple[str, float]]:
    """The other ops that run beside the collective `name`'s own events,
    ns each, the largest first."""
    other = sorted(sp.other)
    starts = [s for s, _, _ in other]
    longest = max((e - s for s, e, _ in other), default=0)
    got: collections.Counter = collections.Counter()
    for s, e in sp.sync.get(name, []):
        i = bisect.bisect_left(starts, e)
        j = bisect.bisect_left(starts, s - longest)
        for a, b, n in other[j:i]:
            if min(b, e) > max(a, s):
                got[n] += min(b, e) - max(a, s)
    return got.most_common(top)


def run_of(ctx):
    """The harness's `Run` that made `ctx`, or None."""
    import scopes

    return scopes._harness_run(ctx)


def hlo(ctx) -> str | None:
    """The compiled step's text, from the harness's `Run`."""
    return getattr(run_of(ctx), "hlo", None)


def device_times(ctx):
    """{device: (collective ns, exposed ns)} in the window, computed and
    logged once per ctx; None on one chip, without the compiled text, or
    where no collective ran."""
    if getattr(ctx, "chips", 1) < 2:
        return None
    done = getattr(ctx, "collective_times", False)
    if done is not False:
        return done
    text = hlo(ctx)
    if not text:
        return None
    r = roles(text)
    out, splits = {}, {}
    for d in ctx.devices:
        sp = split(ctx.trace.ops.get(d, []), r, ctx.lo, ctx.hi)
        own = sp.own()
        t = tracelib.length(own)
        if t > 0:
            out[d] = (t, tracelib.length(tracelib.minus(own, sp.others())))
            splits[d] = sp
    for d, sp in sorted(splits.items()):
        _log(d, sp, ctx.steps)
    ctx.collective_times = out or None
    return ctx.collective_times


def _log(d, sp: Split, steps: int) -> None:
    """The device's collective time by form, the chains' time in flight,
    and what runs beside and inside its largest collective."""
    ms = lambda ns: f"{ns / 1e6 / steps:.3f}"
    other = sp.others()
    sync = tracelib.merge(iv for v in sp.sync.values() for iv in v)
    ends = tracelib.merge(sp.ends)
    flight = tracelib.merge(iv for v in sp.flight.values() for iv in v)
    bare = lambda ivs: tracelib.length(tracelib.minus(ivs, other))
    length = tracelib.length
    print(f"collectives: device {d}: synchronous {ms(length(sync))} ms a "
          f"step, {ms(bare(sync))} exposed; async starts and dones "
          f"{ms(length(ends))}, {ms(bare(ends))} exposed; start to done "
          f"{ms(length(flight))}, {ms(length(flight) - bare(flight))} of it "
          f"under other ops (in neither metric)", flush=True)
    for name, ivs in sorted(sp.flight.items()):
        print(f"collectives: device {d}: in flight to {name}: {len(ivs)} "
              f"times, {ms(sum(e - s for s, e in ivs))} ms a step", flush=True)
    if not sp.sync:
        return
    top = max(sp.sync, key=lambda n: sum(e - s for s, e in sp.sync[n]))
    inside = collections.Counter({n: t for (c, n), t in sp.inside.items()
                                  if c == top})
    print(f"collectives: device {d}: largest {top}, "
          f"{ms(sum(e - s for s, e in sp.sync[top]))} ms a step; beside it "
          f"{[(n, ms(t)) for n, t in beside(sp, top)]}; inside it "
          f"{[(n, ms(t)) for n, t in inside.most_common(8)]}", flush=True)


def planned_bytes(ctx) -> float | None:
    """The plan's own wire bytes a step (`train_step.step_wire_metrics`, the
    numbers the Trainer adds to its `train/wire_bytes/*` counters), from
    the harness's Trainer; None where there is none."""
    trainer = getattr(run_of(ctx), "trainer", None)
    wire = getattr(trainer, "_wire", None)
    return None if wire is None else float(wire["total_bytes"])
