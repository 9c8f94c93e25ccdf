"""The step's parts by the program's own names: device time per named scope,
and the host seconds of the program's set-up spans.

The program puts a `jax.named_scope` around each part of its step (its
`repro.core.obs.spans.SCOPES`: embed, attn, mlp, head_loss, optimizer,
fsdp_gather, fsdp_reduce_scatter), so every op of the compiled step names
its part in the `op_name` metadata, and it times its set-up in host spans
that its `Trainer` records into its metrics registry (`span/<name>`).

`op_scopes` maps each HLO instruction of the compiled step to the innermost
scope its `op_name` names (a fusion by its own metadata); `scope_times`
sums a trace's device self time (`tracelib.self_times`) by that map, and
what no scope claims goes to `unscoped`, so the parts add up to the busy
time.

Where the inputs come from: the harness hands a reader a `ctx` that holds
neither the compiled step's text nor the program's registry, so `inputs`
takes both from the harness's `Run`, the object whose window gaps the
harness handed in as `ctx.host_gaps_s`, found on the reader's call stack.
A program that names no scope gives None for every metric here.
"""

from __future__ import annotations

import re
import sys

import tracelib

UNSCOPED = "unscoped"
# an instruction line of HLO text with its op_name metadata
OP_NAME = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?op_name="([^"]*)"')
WRAPPER = re.compile(r"^(\w+\()+|\)+$")


def scope_of(op_name: str, known) -> str | None:
    """The innermost component of `op_name` that names a known scope, with
    autodiff's wrappers taken off: `transpose(jvp(attn))` is attn's."""
    for part in reversed(op_name.split("/")):
        name = WRAPPER.sub("", part)
        if name in known:
            return name
    return None


def op_scopes(hlo: str, known) -> dict[str, str]:
    """{HLO instruction name: its innermost known scope} over the compiled
    step's text; instructions with no known scope are left out."""
    out = {}
    for line in hlo.splitlines():
        m = OP_NAME.match(line)
        if m:
            scope = scope_of(m.group(2), known)
            if scope is not None:
                out[m.group(1)] = scope
    return out


def scope_times(ops, scopes: dict[str, str], lo: float,
                hi: float) -> dict[str, float]:
    """Device self time in [lo, hi) per scope, ns; ops that `scopes` does
    not map go to `unscoped`. The values add up to the ops' self time."""
    out: dict[str, float] = {}
    for name, t in tracelib.self_times(ops, lo, hi).items():
        k = scopes.get(name, UNSCOPED)
        out[k] = out.get(k, 0.0) + t
    return out


def _harness_run(ctx):
    """The harness's `Run` that made `ctx`: the caller frame's object whose
    `gaps` list is the one in `ctx.host_gaps_s`."""
    gaps = getattr(ctx, "host_gaps_s", None)
    if gaps is None:
        return None
    f = sys._getframe(1)
    while f is not None:
        for v in list(f.f_locals.values()):
            if getattr(v, "gaps", None) is gaps and hasattr(v, "hlo"):
                return v
        f = f.f_back
    return None


def inputs(ctx):
    """(op_scopes or None, {span name: seconds} or None) for `ctx`."""
    try:
        from repro.core.obs.spans import SCOPES, span_times
    except ImportError:              # a program that names no parts
        return None, None
    run = _harness_run(ctx)
    if run is None:
        return None, None
    return (op_scopes(run.hlo, SCOPES) or None,
            span_times(run.trainer.registry) or None)


def _reduce(ctx):
    """(per device, ns of self time per scope in the window; the spans),
    computed and logged once per ctx and kept on it."""
    done = getattr(ctx, "scope_reduction", None)
    if done is not None:
        return done
    scopes, spans = inputs(ctx)
    per_dev = None
    if scopes is not None:
        per_dev = {d: scope_times(ctx.trace.ops[d], scopes, ctx.lo, ctx.hi)
                   for d in ctx.devices}
        _log_scopes(ctx, scopes, per_dev)
    for name, secs in (spans or {}).items():
        print(f"span {name}: {secs:.4f} s", flush=True)
    ctx.scope_reduction = (per_dev, spans)
    return ctx.scope_reduction


def _log_scopes(ctx, scopes, per_dev):
    d = ctx.devices[0]
    times = per_dev[d]
    busy = tracelib.length(tracelib.busy(ctx.trace, d, ctx.lo, ctx.hi))
    total = sum(times.values())
    for name, t in sorted(times.items(), key=lambda kv: -kv[1]):
        print(f"scope {name}: {t / 1e6 / ctx.steps:.3f} ms/step, "
              f"{100 * t / busy:.2f}% of busy", flush=True)
    print(f"scopes + {UNSCOPED}: {total / 1e9:.6f} s against busy "
          f"{busy / 1e9:.6f} s ({100 * (total / busy - 1):+.3f}%)",
          flush=True)
    share = times.get(UNSCOPED, 0.0) / busy
    if share > 0.10:
        ops = {o.name: o for o in ctx.trace.ops[d]}
        own = tracelib.self_times(ctx.trace.ops[d], ctx.lo, ctx.hi)
        top = sorted((t, n) for n, t in own.items() if n not in scopes)[-5:]
        for t, n in reversed(top):
            print(f"  {UNSCOPED}: {t / 1e6 / ctx.steps:.3f} ms/step "
                  f"{tracelib.label(ops[n])}", flush=True)


def device_ms(ctx, *names: str) -> float | None:
    """Device self time of the named scopes, ms per step, the mean over the
    cell's devices; None where the program names no scope."""
    per_dev = _reduce(ctx)[0]
    if per_dev is None:
        return None
    ns = [sum(t.get(n, 0.0) for n in names) for t in per_dev.values()]
    return sum(ns) / len(ns) / 1e6 / ctx.steps


def span_s(ctx, name: str) -> float | None:
    """Host seconds the program spent in its span `name`, None where it
    recorded none."""
    spans = _reduce(ctx)[1]
    return None if spans is None else spans.get(name)
