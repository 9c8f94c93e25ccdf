"""apply_stack: run a homogeneous layer stack under SimpleFSDP scheduling.

This module is the JAX incarnation of the paper's TorchInductor *backend*
passes (SS3.2). PyTorch reorders already-built IR nodes; XLA exposes no user
IR pass, so we get the same schedules by *constructing* the dataflow so each
communication is independent of the compute it must overlap (DESIGN.md SS2):

  reorder=False  ("vanilla")
      lax.scan(checkpoint(gather -> compute)); every layer's all-gather is
      data-adjacent to its compute — fully exposed communication, exactly the
      paper's unoptimized trace. Gathers are still bucketed per `plan`.
      Backward collectives come from the `gather_group` custom_vjp.

  reorder=True   (bucketing + reordering, paper Fig. 2)
      A hand-scheduled double-buffered scan with a custom VJP, pipelined at
      BUCKET granularity.  The layer is an ordered chain of *segments*
      (models/common.BlockSegments — e.g. attn / mlp); the bucket plan is
      split at segment boundaries so every bucket belongs to exactly one
      segment, and the schedule realizes Algorithm 1's premise inside the
      layer, not just across layers:

        forward  — the scan carry holds the gathered FIRST bucket group of
                   layer i; segment s's compute overlaps segment s+1's
                   all-gather (AG_{s+1} "before Wa_s"), and the last segment
                   prefetches layer i+1's first bucket across the layer
                   boundary. Saves ONLY per-layer block inputs (= full
                   activation checkpointing) — the carry now holds one
                   bucket group instead of a whole gathered layer.
        backward — re-gathers bucket by bucket while the layer recomputes
                   segment by segment (re-gather = the selective-AC
                   MUST_RECOMPUTE semantics): segment s's recompute overlaps
                   segment s+1's gather, the last segment prefetches layer
                   i-1's first bucket, and under rs_delay the previous
                   layer's per-bucket reduce-scatters are interleaved with
                   this layer's backward segment sweep ("Wr12 before RS34",
                   one RS issue point per bucket).

      Models that declare no segments (or cfg.segment_prefetch=False) run
      the same machinery with a single whole-layer segment, which is exactly
      the pre-v2 schedule. The Table-6 ablation flags (ag_before_wait_fwd/
      bwd, rs_delay) keep their meanings at segment granularity; the "after"
      variants insert an optimization_barrier to force the sequential
      schedule they name.

The first (forward) / last (backward) iteration is peeled out of the scan so
every carried value gets its true varying-manual-axes (vma) type from real
computation — scan carries must type-match exactly under shard_map vma.

Block contract (unsegmented):
    block_fn(params_full, consts, x) -> (y, aux)
      params_full : pytree of TP-local compute tensors (structure == metas)
      consts      : pytree treated as constants (rope caches, masks) — zero
                    cotangent (stop-grad)
      x / y       : activation carry pytree (same structure both sides)
      aux         : dict of scalars summed over layers (MoE aux loss etc.)

Segmented contract (models/common.BlockSegments): fns[s](params, consts,
state) -> state, where `params` is the full metas-shaped pytree with ONLY
segment s's leaves populated (others None — touching a foreign leaf fails at
trace time, which is what keeps the bucket pipelining honest), state_0 is the
block input x and the last segment returns (y, aux).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import collectives as coll
from repro.core.bucketing import (BucketPlan, assign_segments, plan_for,
                                  split_plan_at_segments)
from repro.core.dist import DistConfig
from repro.core.meta import ParamMeta, named_leaves
from repro.core.remat import maybe_remat, resolve_segment_policies


def _meta_leaves(metas_tree):
    is_meta = lambda x: isinstance(x, ParamMeta)
    leaves, treedef = jax.tree_util.tree_flatten(metas_tree, is_leaf=is_meta)
    return leaves, treedef


def _zero_cotangent(x):
    def one(v):
        if jnp.issubdtype(v.dtype, jnp.floating) or jnp.issubdtype(
                v.dtype, jnp.complexfloating):
            return jnp.zeros(v.shape, v.dtype)
        return np.zeros(v.shape, jax.dtypes.float0)
    return jax.tree.map(one, x)


def apply_stack(block_fn: Callable, metas_tree, cfg: DistConfig,
                stacked, consts, x, plan: BucketPlan | None = None,
                block_stats=None, segments=None, remat=None):
    """Run the layer stack; returns (y, aux_sums).

    `segments` is an optional models/common.BlockSegments declaring the
    ordered segment chain of one block; with cfg.segment_prefetch it enables
    bucket-granular pipelining on the reorder path and makes the auto
    planners respect segment boundaries, so the planned partition is the
    one the schedule executes.

    `remat` is the resolved per-segment policy vector (one entry per
    segment; `core/memory`'s auto-SAC planner output).  When omitted it is
    resolved from ``cfg.remat`` — a single policy broadcasts, the vector
    grammar ("attn=full,mlp=fsdp_only") maps segments by name, and the
    unresolved ``"auto:<GB>"`` form raises pointedly (it must be resolved by
    `core/api.plan_parallel` before trace time).  On the vanilla path a
    non-uniform vector checkpoints each segment separately (gathers INSIDE
    the wrap, so `fsdp_only` still drops them); on the prefetch path —
    whose hand-written VJP already saves only block inputs and re-gathers
    per bucket — residual-dropping entries (`full`/`save_dots`) bound the
    backward recompute residency per segment.
    """
    if plan is None:
        plan = plan_for(metas_tree, cfg, block_stats, segments=segments)
    seg_names = tuple(segments.names) \
        if segments is not None and len(segments.fns) > 1 else ()
    if remat is None:
        remat = resolve_segment_policies(cfg.remat, seg_names)
    remat = tuple(remat)
    if len(remat) != max(1, len(seg_names)):
        raise ValueError(
            f"remat vector {remat} does not match the block's "
            f"{max(1, len(seg_names))} segment(s) {seg_names or '(block)'}")
    if cfg.reorder:
        return _prefetch_stack(block_fn, metas_tree, cfg, plan, stacked,
                               consts, x, segments, remat)
    return _vanilla_stack(block_fn, metas_tree, cfg, plan, stacked, consts,
                          x, segments, remat)


# ---------------------------------------------------------------------------
# Vanilla: scan(remat(gather -> compute)). Exposed comm; autodiff backward.
# ---------------------------------------------------------------------------
def _segmented_vanilla_layer(block_fn, metas_tree, cfg, plan, consts,
                             segments, policies):
    """One layer as a per-segment checkpointed chain (non-uniform remat).

    Each segment gathers ITS buckets inside its own `jax.checkpoint` wrap
    (via the differentiable `gather_group`), so a `fsdp_only` entry drops
    exactly that segment's gathered params while a neighbouring `none`
    entry keeps its own — the auto-SAC planner's per-segment policy vector,
    realized on the autodiff path."""
    metas, treedef = _meta_leaves(metas_tree)
    names = [k for k, _ in named_leaves(metas_tree)]
    seg_of = assign_segments(names, segments.param_globs, segments.names)
    exec_plan = split_plan_at_segments(plan, metas_tree, segments)
    S = len(segments.fns)
    seg_idxs = [sorted(i for i, s in enumerate(seg_of) if s == s_id)
                for s_id in range(S)]
    pos_in = [{i: p for p, i in enumerate(idxs)} for idxs in seg_idxs]
    seg_groups: list[list[list[int]]] = [[] for _ in range(S)]
    seg_precs: list[list[str]] = [[] for _ in range(S)]
    exec_precs = exec_plan.group_precisions(metas_tree, cfg)
    for grp, prec in zip(exec_plan.index_groups(metas_tree), exec_precs):
        seg_groups[seg_of[grp[0]]].append(grp)
        seg_precs[seg_of[grp[0]]].append(prec)

    def seg_run(s, shards_s, state):
        full: list = [None] * len(metas)
        for grp, prec in zip(seg_groups[s], seg_precs[s]):
            outs = coll.gather_group(
                tuple(shards_s[pos_in[s][i]] for i in grp),
                tuple(metas[i] for i in grp), cfg, prec)
            for i, o in zip(grp, outs):
                full[i] = o
        params = jax.tree_util.tree_unflatten(treedef, full)
        return segments.fns[s](params, consts, state)

    def layer(xc, layer_shards):
        shard_leaves = treedef.flatten_up_to(layer_shards)
        state = xc
        for s in range(S):
            shards_s = tuple(shard_leaves[i] for i in seg_idxs[s])
            state = maybe_remat(
                lambda sh, st, s=s: seg_run(s, sh, st),
                policies[s])(shards_s, state)
        return state                     # last segment returns (y, aux)

    return layer


def _vanilla_stack(block_fn, metas_tree, cfg, plan, stacked, consts, x,
                   segments=None, policies=None):
    metas, treedef = _meta_leaves(metas_tree)
    leaves = treedef.flatten_up_to(stacked)
    L = leaves[0].shape[0]

    policies = policies or (cfg.remat,)
    if (len(set(policies)) > 1 and segments is not None
            and len(segments.fns) > 1):
        layer = _segmented_vanilla_layer(block_fn, metas_tree, cfg, plan,
                                         consts, segments, policies)
    else:
        def layer(xc, layer_shards):
            params = coll.replicate_tree(layer_shards, metas_tree, cfg, plan)
            return block_fn(params, consts, xc)

        layer = maybe_remat(layer, policies[0])

    # peel layer 0 (gives the aux accumulator its true vma type)
    y, aux = layer(x, jax.tree_util.tree_unflatten(
        treedef, [l[0] for l in leaves]))
    if L == 1:
        return y, aux

    def body(carry, layer_shards):
        xc, aux = carry
        y, aux_l = layer(xc, layer_shards)
        return (y, jax.tree.map(jnp.add, aux, aux_l)), None

    rest = jax.tree_util.tree_unflatten(treedef, [l[1:] for l in leaves])
    (y, aux), _ = lax.scan(body, (y, aux), rest)
    return y, aux


# ---------------------------------------------------------------------------
# Prefetch: bucket-granular double-buffered scan with hand-written VJP.
# ---------------------------------------------------------------------------
def _prefetch_stack(block_fn, metas_tree, cfg, plan, stacked, consts, x,
                    segments=None, policies=None):
    metas, treedef = _meta_leaves(metas_tree)
    names = [k for k, _ in named_leaves(metas_tree)]
    stacked_leaves = treedef.flatten_up_to(stacked)
    L = stacked_leaves[0].shape[0]
    shard_shapes = [m.shard_shape(cfg) for m in metas]

    if (segments is not None and cfg.segment_prefetch
            and len(segments.fns) > 1):
        seg_fns = tuple(segments.fns)
        seg_of = assign_segments(names, segments.param_globs, segments.names)
        # the executed partition: split at segment boundaries, segment-major
        # (the SAME rewrite exposed_comm_time scores — one implementation)
        plan = split_plan_at_segments(plan, metas_tree, segments)
    else:
        # single whole-layer segment == the pre-segmentation schedule
        seg_fns = (lambda params, cst, state: block_fn(params, cst, state),)
        seg_of = [0] * len(names)
    # Per-segment remat on the prefetch path: the hand-written VJP already
    # saves only block inputs and re-gathers per bucket (fsdp_only-or-
    # better semantics by construction), so only the residual-DROPPING
    # policies change anything — they checkpoint the segment so the
    # backward recompute (`one_bwd`'s jax.vjp sweep) holds that segment's
    # input instead of all its intermediates. `none`/`fsdp_only` entries
    # keep the schedule exactly as-is (values are identical either way;
    # this is a residency knob, modeled by core/memory's simulator).
    if policies is not None:
        if len(policies) != len(seg_fns):
            # segments declared but not active (cfg.segment_prefetch off):
            # collapse the vector to its most memory-aggressive entry so the
            # whole-layer wrap never saves more than the vector promised
            from repro.core.remat import most_aggressive
            policies = (most_aggressive(policies),) * len(seg_fns)
        seg_fns = tuple(
            maybe_remat(fn, p) if p in ("full", "save_dots") else fn
            for fn, p in zip(seg_fns, policies))
    S = len(seg_fns)

    seg_groups: list[list[list[int]]] = [[] for _ in range(S)]
    seg_precs: list[list[str]] = [[] for _ in range(S)]
    for grp, prec in zip(plan.index_groups(metas_tree),
                         plan.group_precisions(metas_tree, cfg)):
        seg_groups[seg_of[grp[0]]].append(grp)
        seg_precs[seg_of[grp[0]]].append(prec)
    # flat group order is segment-major — the RS finalization order
    flat_groups = [g for s in range(S) for g in seg_groups[s]]
    flat_precs = [p for s in range(S) for p in seg_precs[s]]
    seg_base = [sum(len(seg_groups[t]) for t in range(s)) for s in range(S)]
    seg_idxs = [sorted(i for g in seg_groups[s] for i in g)
                for s in range(S)]
    pos_in = [{i: p for p, i in enumerate(idxs)} for idxs in seg_idxs]

    def slice_seg(leaves, idx, s):
        return [lax.dynamic_index_in_dim(leaves[i], idx, 0, keepdims=False)
                for i in seg_idxs[s]]

    def gather_seg(leaves, idx, s, barrier=None):
        """Gather segment s's bucket groups of layer `idx` (one packed AG
        per vma class per group); returns tensors ordered as seg_idxs[s]."""
        shards = slice_seg(leaves, idx, s)
        if barrier is not None:
            # Table-6 'after' placement: tie the gather's inputs to the
            # previous compute so it cannot be scheduled ahead of it.
            # optimization_barrier JOINS the vma of everything it ties, so a
            # raw tie would up-vary TP-replicated shards; instead tie each
            # shard to a zero scalar token derived from the barrier value and
            # psum-reduced down to that shard's own vma class.
            lf = jax.tree.leaves(barrier)[0]
            base = (lf.ravel()[:1].sum() * 0).astype(jnp.float32)
            tokens: dict = {}

            def tok(vma):
                key = frozenset(vma)
                if key not in tokens:
                    extra = tuple(a for a in jax.typeof(base).vma
                                  if a not in key)
                    tokens[key] = lax.psum(base, extra) if extra else base
                return tokens[key]

            shards = [
                lax.optimization_barrier((sh, tok(jax.typeof(sh).vma)))[0]
                for sh in shards
            ]
        full: list = [None] * len(shards)
        for grp, prec in zip(seg_groups[s], seg_precs[s]):
            outs = coll.gather_group_fwd_raw(
                [shards[pos_in[s][i]] for i in grp],
                [metas[i] for i in grp], cfg, prec)
            for i, o in zip(grp, outs):
                full[pos_in[s][i]] = o
        return full

    def seg_apply(s, g_seg, cst, state):
        """Run segment s on its gathered tensors (masked full-tree view)."""
        full: list = [None] * len(metas)
        for i, t in zip(seg_idxs[s], g_seg):
            full[i] = t
        params = jax.tree_util.tree_unflatten(treedef, full)
        return seg_fns[s](params, cst, state)

    # -------------------------------------------------- forward (primal) --
    def one_fwd(leaves, g, xc, idx, nxt_idx, cst, prefetch_last=True):
        """Layer idx's segment chain; bucket s+1 gathers around segment s's
        compute; the last segment prefetches layer nxt_idx's first bucket."""
        state = xc
        for s in range(S):
            last = s == S - 1
            t_idx, t_seg = (nxt_idx, 0) if last else (idx, s + 1)
            do = (not last) or prefetch_last
            g_next = None
            if do and cfg.ag_before_wait_fwd:
                g_next = gather_seg(leaves, t_idx, t_seg)   # AG before Wa
            state = seg_apply(s, g, cst, state)
            if do and not cfg.ag_before_wait_fwd:
                g_next = gather_seg(leaves, t_idx, t_seg, barrier=state)
            g = g_next
        y, aux = state
        return y, aux, g   # g = gathered first bucket of layer nxt_idx

    def fwd_scan(leaves, x0, cst):
        g0 = gather_seg(leaves, 0, 0)   # exposed prologue gather (Fig. 2)
        if L == 1:
            y, aux, _ = one_fwd(leaves, g0, x0, 0, 0, cst,
                                prefetch_last=False)
            return y, aux, jax.tree.map(lambda v: v[None], x0)

        y, aux, g1 = one_fwd(leaves, g0, x0, 0, 1, cst)   # peeled layer 0

        def body(carry, idx):
            xc, aux, g = carry
            nxt = jnp.minimum(idx + 1, L - 1)     # last prefetch is a no-op
            yb, aux_l, g_next = one_fwd(leaves, g, xc, idx, nxt, cst)
            return (yb, jax.tree.map(jnp.add, aux, aux_l), g_next), xc

        (y, aux, _), xs_rest = lax.scan(body, (y, aux, g1),
                                        jnp.arange(1, L))
        xs = jax.tree.map(lambda a, b: jnp.concatenate([a[None], b], 0),
                          x0, xs_rest)
        return y, aux, xs

    # ----------------------------------------------------------- backward --
    def bwd_scan(leaves, xs, dy, daux, cst):
        x_treedef = jax.tree.structure(dy)
        xs_leaves = jax.tree.leaves(xs)
        G = len(flat_groups)

        def slice_x(idx):
            sliced = [lax.dynamic_index_in_dim(v, idx, 0, keepdims=False)
                      for v in xs_leaves]
            return jax.tree_util.tree_unflatten(x_treedef, sliced)

        def pack_seg(s, dg_seg):
            """Segment s's param cotangents -> packed ct per bucket group."""
            return [
                coll.pack_grad_bucket([dg_seg[pos_in[s][i]] for i in grp],
                                      [metas[i] for i in grp], cfg)
                for grp in seg_groups[s]
            ]

        def finalize_group(gi, ct, out):
            """One bucket's RS -> per-leaf local grad chunks into `out`."""
            grp = flat_groups[gi]
            parts = coll.finalize_grad_bucket(
                ct, [metas[i] for i in grp], cfg,
                [shard_shapes[i] for i in grp], flat_precs[gi])
            for i, p in zip(grp, parts):
                out[i] = p

        def finalize(pending):
            out: list = [None] * len(metas)
            for gi, ct in enumerate(pending):
                finalize_group(gi, ct, out)
            return out

        def one_bwd(g_first, idx, dx, prv_idx, prefetch, emit=None):
            """Recompute + vjp layer idx, segment-pipelined.

            g_first: gathered first bucket group of layer idx. The forward
            recompute gathers bucket s+1 around segment s (re-gather =
            selective-AC); the backward segment sweep interleaves the
            delayed per-bucket RS of `emit` (the previous layer's pending
            grads, rs_delay) and the cross-layer prefetch of layer
            prv_idx's first bucket rides the schedule flag.
            """
            x_l = slice_x(idx)
            # ---- forward recompute, bucket-pipelined gathers ----
            vjps: list = [None] * S
            state = x_l
            g = g_first
            g_prev = None
            for s in range(S):
                last = s == S - 1
                if cfg.ag_before_wait_bwd:
                    if not last:
                        g_next = gather_seg(leaves, idx, s + 1)
                    elif prefetch:
                        g_prev = gather_seg(leaves, prv_idx, 0)
                state, vjps[s] = jax.vjp(
                    lambda gl, st, s=s: seg_apply(s, gl, cst, st), g, state)
                if not cfg.ag_before_wait_bwd and not last:
                    g_next = gather_seg(leaves, idx, s + 1, barrier=state)
                if not last:
                    g = g_next
            # ---- backward segment sweep, delayed RS interleaved ----
            emitted = [None] * len(metas) if emit is not None else None
            cts: list = [None] * G
            ct = (dx, daux)
            for s in reversed(range(S)):
                if emit is not None:
                    lo = (S - 1 - s) * G // S
                    hi = (S - s) * G // S
                    for gi in range(lo, hi):   # one RS issue point per bucket
                        finalize_group(gi, emit[gi], emitted)
                dg_seg, ct = vjps[s](ct)
                for k, packed in enumerate(pack_seg(s, dg_seg)):
                    cts[seg_base[s] + k] = packed
            dx_new = ct
            if prefetch and not cfg.ag_before_wait_bwd:
                g_prev = gather_seg(leaves, prv_idx, 0, barrier=dx_new)
            return cts, dx_new, g_prev, emitted

        # peeled layer L-1
        gL = gather_seg(leaves, L - 1, 0)
        pending, dx, g_cur, _ = one_bwd(gL, L - 1, dy, max(L - 2, 0),
                                        prefetch=L > 1)
        if L == 1:
            d_last = finalize(pending)
            return [d[None] for d in d_last], dx
        if not cfg.rs_delay:
            d_top = finalize(pending)  # layer L-1, reduced immediately

        def body(carry, idx):
            dx, g_cur, pending = carry
            prv = jnp.maximum(idx - 1, 0)
            if cfg.rs_delay:
                pending_new, dx_new, g_prev, emitted = one_bwd(
                    g_cur, idx, dx, prv, prefetch=True, emit=pending)
            else:
                pending_new, dx_new, g_prev, _ = one_bwd(
                    g_cur, idx, dx, prv, prefetch=True)
                emitted = finalize(pending_new)   # layer idx, immediate
                pending_new = pending
            return (dx_new, g_prev, pending_new), emitted

        (dx, _, pending), emitted = lax.scan(
            body, (dx, g_cur, pending), jnp.arange(L - 2, -1, -1))

        # Reassemble per-layer grad stacks. Scan step j handled idx = L-2-j.
        if cfg.rs_delay:
            d0 = finalize(pending)   # layer 0 grads still pending
            # emitted[j] = layer L-1-j  ->  flip = layers 1..L-1
            dstack = [
                jnp.concatenate([p0[None], jnp.flip(e, 0)], axis=0)
                for p0, e in zip(d0, emitted)
            ]
        else:
            # emitted[j] = layer L-2-j  ->  flip = layers 0..L-2
            dstack = [
                jnp.concatenate([jnp.flip(e, 0), dt[None]], axis=0)
                for dt, e in zip(d_top, emitted)
            ]
        return dstack, dx

    # ------------------------------------------------------- custom_vjp ----
    @jax.custom_vjp
    def run(stacked_, consts_, x_):
        leaves = treedef.flatten_up_to(stacked_)
        y, aux, _ = fwd_scan(leaves, x_, consts_)
        return y, aux

    def run_fwd(stacked_, consts_, x_):
        leaves = treedef.flatten_up_to(stacked_)
        y, aux, xs = fwd_scan(leaves, x_, consts_)
        return (y, aux), (leaves, consts_, xs)

    def run_bwd(res, cts):
        leaves, consts_, xs = res
        dy, daux = cts
        dstack_leaves, dx = bwd_scan(leaves, xs, dy, daux, consts_)
        dstacked = jax.tree_util.tree_unflatten(treedef, dstack_leaves)
        return dstacked, _zero_cotangent(consts_), dx

    run.defvjp(run_fwd, run_bwd)
    return run(stacked, consts, x)
