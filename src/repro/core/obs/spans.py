"""Named parts of a training step, on the device and on the host.

Device: `SCOPES` are the `jax.named_scope` names the program puts around
the parts of its step, so that every op of the compiled step carries its
part in the `op_name` metadata (forward, remat recompute and the
`transpose(jvp(...))` backward alike). A profiler trace of the step can
then be summed per part. Where they sit:

  embed                the token lookup and its backward scatter-add
                       (models/layers.embed_apply)
  attn, mlp            the block's residual branches with their pre-norms
                       (models/dense._attn_half / _mlp_half; the names of
                       the `block_segments` segments)
  head_loss            final norm, LM head and cross-entropy
                       (models/dense.stage_loss)
  optimizer            grad norm, clip and the AdamW update
                       (optim/adamw.apply_adamw)
  fsdp_gather          pack, all-gather and unpack of a parameter bucket
                       (core/collectives.gather_group_fwd_raw)
  fsdp_reduce_scatter  copy-in, reduce-scatter and split of a gradient
                       bucket (core/collectives.pack_grad_bucket /
                       finalize_grad_bucket)

The call sites spell the names out (this package imports the planners, which
import the collectives, so the collectives cannot import it back); a test
holds them to this tuple. Scopes nest: an FSDP gather inside `embed` is
`fsdp_gather`'s, and a reader takes an op's innermost scope. No scope sits
between a Pallas call and its kernel's own name, which stays the op_name's
last component but one.

Host: `span(name, registry)` marks a stretch of host work both ways at
once: a `jax.profiler.TraceAnnotation`, so that it lands on the profiler's
host line on the device ops' clock, and the stretch's seconds observed into
the registry's `span/<name>` histogram. `gc_span` opens a `gc` span around
every Python garbage collection while it is active.
"""

from __future__ import annotations

import contextlib
import gc
import time

import jax

SCOPES = ("embed", "attn", "mlp", "head_loss", "optimizer", "fsdp_gather",
          "fsdp_reduce_scatter")
PREFIX = "span/"


class span:
    """`with span("parallelize", registry): ...` — a profiler annotation
    and a `span/<name>` histogram observation of its seconds. Spans nest;
    each records its own full length."""

    __slots__ = ("name", "registry", "_note", "_t0")

    def __init__(self, name: str, registry):
        self.name = name
        self.registry = registry

    def __enter__(self):
        self._note = jax.profiler.TraceAnnotation(self.name)
        self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.registry.histogram(PREFIX + self.name).observe(
            time.perf_counter() - self._t0)
        self._note.__exit__(*exc)
        return False


def span_times(registry) -> dict[str, float]:
    """{span name: total seconds} of every span recorded into `registry`."""
    return {n[len(PREFIX):]: registry.histogram(n).sum
            for n in registry.names() if n.startswith(PREFIX)}


@contextlib.contextmanager
def gc_span(registry):
    """While active, every Python garbage collection is a `gc` span: a
    slow step or a device idle gap that sits on a collection shows it on
    the trace's host line."""
    open_spans: list[span] = []

    def on_gc(phase, info):
        if phase == "start":
            open_spans.append(span("gc", registry).__enter__())
        elif open_spans:
            open_spans.pop().__exit__(None, None, None)

    gc.callbacks.append(on_gc)
    try:
        yield
    finally:
        gc.callbacks.remove(on_gc)
