"""FSDP collectives: the JAX port of the paper's ``ReplicateComputation``.

Three layers, bottom-up:

  1. raw pack / all-gather / unpack / reduce-scatter helpers (used directly by
     the hand-scheduled backward in `core/stack.py`);
  2. `gather_group` — a ``jax.custom_vjp`` that gathers a *group* of parameter
     shards (group of one == the paper's per-parameter parametrization;
     group of many == a TorchInductor-style bucket: one flat buffer, ONE
     all-gather, copy-out slices) and whose backward is the matching single
     reduce-scatter with ``Partial(avg)`` gradient placement and
     ``reduce_dtype`` casting (paper Fig. 1(2) + SS4 mixed precision);
  3. `replicate` — per-parameter convenience wrapper.

Everything runs *inside* ``shard_map``: a "shard" here is the per-device
``(chunk,)`` / ``(1, chunk)`` slice of the flat storage layout (core/meta.py).
Gathered tensors are tagged with ``checkpoint_name(..., 'fsdp_gather')`` so the
remat policy in `core/remat.py` re-issues the all-gather in the backward pass
instead of saving full parameters — the paper's selective-AC trick (Fig. 1(1)).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from repro.core.dist import DistConfig, precision_codecs
from repro.core.meta import ParamMeta, flatten_local, unflatten_local
from repro.kernels.quant import ops as quant_ops

FSDP_GATHER_NAME = "fsdp_gather"


def default_precision(cfg: DistConfig) -> str:
    """The wire precision a collective runs at when its bucket has no
    per-bucket annotation: the config's own value, with 'auto' degrading to
    bf16 (under 'auto' the resolved plan is what carries fp8 buckets)."""
    return "bf16" if cfg.comm_precision == "auto" else cfg.comm_precision


def _quant_wire(buf: jax.Array, codec: str | None,
                stochastic: bool) -> jax.Array:
    """Encode+decode the flat buffer to the wire codec ahead of the
    collective.  Because dequantization commutes with all-gather (each
    rank's slice decodes independently) and with psum's direct reduce when
    every contribution is quantized exactly once, this local roundtrip is
    numerically identical to shipping the quantized payload — the cost
    model prices the actual wire bytes separately (irgraph.wire_bytes)."""
    if codec is None:
        return buf
    return quant_ops.roundtrip(buf, codec, stochastic=stochastic)


def _fsdp_axes(cfg: DistConfig):
    return cfg.fsdp_axes if len(cfg.fsdp_axes) > 1 else cfg.fsdp_axes[0]


def _squeeze_tp(shard: jax.Array, meta: ParamMeta) -> jax.Array:
    """Inside shard_map a TP param shard arrives as (1, chunk) -> (chunk,)."""
    return shard[0] if meta.tp_dim is not None else shard


# ---------------------------------------------------------------------------
# 1. Raw primitives (no autodiff attached).
# ---------------------------------------------------------------------------
def pack_shards(shards: Sequence[jax.Array]) -> jax.Array:
    """Concatenate per-param local chunks into one flat bucket buffer."""
    if len(shards) == 1:
        return shards[0].reshape(-1)
    return jnp.concatenate([s.reshape(-1) for s in shards])


def gather_flat(buf: jax.Array, cfg: DistConfig) -> jax.Array:
    """One all-gather of the bucket buffer -> (fsdp_size, bucket_len).

    Gathers flat (tiled) and reshapes: the TPU compiler lays a non-tiled
    (fsdp, bucket_len) result out in (fsdp, 128) tiles, and the code it
    then emits for a large bucket (the embedding's) takes minutes to
    compile; the flat form takes seconds."""
    if cfg.fsdp_size == 1:
        return buf[None]
    return lax.all_gather(buf, _fsdp_axes(cfg), tiled=True).reshape(
        cfg.fsdp_size, -1)


def unpack_gathered(g: jax.Array, metas: Sequence[ParamMeta],
                    cfg: DistConfig) -> list[jax.Array]:
    """Copy-out: slice the (fsdp, bucket_len) buffer back into params."""
    outs, off = [], 0
    for m in metas:
        chunk = m.chunk_len(cfg)
        seg = lax.slice_in_dim(g, off, off + chunk, axis=1)
        outs.append(unflatten_local(seg.reshape(-1), m, cfg))
        off += chunk
    return outs


def pack_grads(grads: Sequence[jax.Array], metas: Sequence[ParamMeta],
               cfg: DistConfig) -> jax.Array:
    """Copy-in: full TP-local grads -> (fsdp, bucket_len) RS layout."""
    cols = []
    for g, m in zip(grads, metas):
        flat = flatten_local(g, m, cfg)
        cols.append(flat.reshape(cfg.fsdp_size, m.chunk_len(cfg)))
    return cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)


def reduce_scatter_flat(ct: jax.Array, cfg: DistConfig) -> jax.Array:
    """One reduce-scatter of the grad bucket -> local (bucket_len,) chunk.

    Scatters the flattened buffer (tiled): the same rows reach the same
    ranks as a scatter over dim 0 of the (fsdp, bucket_len) view, but the
    TPU compiler spends minutes on the 2-D form of a large bucket (the
    embedding's) and seconds on the 1-D one."""
    if cfg.fsdp_size == 1:
        return ct[0]
    return lax.psum_scatter(ct.reshape(-1), _fsdp_axes(cfg),
                            scatter_dimension=0, tiled=True)


def split_grad_chunks(flat: jax.Array, metas: Sequence[ParamMeta],
                      cfg: DistConfig, shard_shapes: Sequence[tuple]) \
        -> list[jax.Array]:
    outs, off = [], 0
    for m, ss in zip(metas, shard_shapes):
        chunk = m.chunk_len(cfg)
        outs.append(
            lax.slice_in_dim(flat, off, off + chunk, axis=0).reshape(ss))
        off += chunk
    return outs


# ---------------------------------------------------------------------------
# Forward / backward halves shared by custom_vjp and core/stack.py.
# ---------------------------------------------------------------------------
def _vma_classes(metas: Sequence[ParamMeta]) -> list[list[int]]:
    """Split a bucket into vma classes. TP-sharded storage is varying over
    the TP mesh axis while TP-replicated storage is invariant there; packing
    them into ONE buffer would erase that distinction (shard_map's vma type
    system has no sound downcast), so each class gets its own flat buffer.
    A bucket therefore lowers to at most two collectives."""
    cls: dict[bool, list[int]] = {}
    for i, m in enumerate(metas):
        cls.setdefault(m.tp_dim is not None, []).append(i)
    return list(cls.values())


@jax.named_scope("fsdp_gather")            # core/obs.SCOPES
def gather_group_fwd_raw(shards: Sequence[jax.Array],
                         metas: Sequence[ParamMeta],
                         cfg: DistConfig,
                         precision: str | None = None) -> list[jax.Array]:
    """Pack -> one AG per vma class -> unpack; returns compute tensors.

    `precision` is the bucket's resolved wire precision (None = the config
    default): a quantized AG encodes the packed buffer to per-chunk-scaled
    fp8 (deterministic round-to-nearest — every rank must decode identical
    params) before the gather."""
    ag_codec, _ = precision_codecs(precision or default_precision(cfg))
    flats = [_squeeze_tp(s, m) for s, m in zip(shards, metas)]
    if cfg.gather_in_param_dtype:
        flats = [f.astype(cfg.param_dtype) for f in flats]
    outs: list = [None] * len(flats)
    for idxs in _vma_classes(metas):
        buf = pack_shards([flats[i] for i in idxs])
        buf = _quant_wire(buf, ag_codec, stochastic=False)
        g = checkpoint_name(gather_flat(buf, cfg), FSDP_GATHER_NAME)
        sub = unpack_gathered(g, [metas[i] for i in idxs], cfg)
        for i, o in zip(idxs, sub):
            outs[i] = o
    if not cfg.gather_in_param_dtype:
        outs = [o.astype(cfg.param_dtype) for o in outs]
    return outs


def rs_dtype(cfg: DistConfig):
    return jnp.bfloat16 if cfg.grad_compression else cfg.reduce_dtype


@jax.named_scope("fsdp_reduce_scatter")    # core/obs.SCOPES
def pack_grad_bucket(grads_full: Sequence[jax.Array],
                     metas: Sequence[ParamMeta],
                     cfg: DistConfig) -> tuple[jax.Array, ...]:
    """Copy-in: full TP-local grads -> per-vma-class (fsdp, len) buffers."""
    gs = [g.astype(rs_dtype(cfg)) for g in grads_full]
    return tuple(
        pack_grads([gs[i] for i in idxs], [metas[i] for i in idxs], cfg)
        for idxs in _vma_classes(metas)
    )


@jax.named_scope("fsdp_reduce_scatter")    # core/obs.SCOPES
def finalize_grad_bucket(cts: tuple, metas: Sequence[ParamMeta],
                         cfg: DistConfig,
                         shard_shapes: Sequence[tuple],
                         precision: str | None = None) -> list[jax.Array]:
    """One RS per vma class (mean over DP) -> per-param local grad chunks.

    A quantized RS ('fp8'/'fp8_ef') encodes each rank's contribution to
    per-chunk-scaled fp8 with STOCHASTIC rounding before the psum-scatter —
    one quantization per contribution, direct-reduced (the qgZ shape), and
    unbiased, which is the condition Markov et al.'s convergence analysis
    needs; 'fp8_ef' additionally compensates the reduced shard's wire
    format with the persistent error-feedback accumulator in the optimizer
    (optim/adamw.py — gradient state cannot thread through this vjp).

    Cross-pod (HSDP) and TP-replication gradient sums are NOT issued here:
    under shard_map's varying-manual-axes (vma) tracking, the transpose of
    the automatic `pvary` at each replicated->varying boundary inserts
    exactly the required psum over 'pod'/'model', so cotangents arrive at
    this reduce-scatter already summed over every axis the parameter is
    replicated on. (Verified by tests/dist_harness.py against dense refs.)
    """
    _, rs_codec = precision_codecs(precision or default_precision(cfg))
    outs: list = [None] * len(metas)
    for ct, idxs in zip(cts, _vma_classes(metas)):
        ct = _quant_wire(ct, rs_codec, stochastic=True)
        local = reduce_scatter_flat(ct, cfg)
        # Partial(avg): mean over the full DP domain. Combined with a
        # per-device local-mean loss this is the global-batch mean gradient.
        local = local.astype(cfg.reduce_dtype) / cfg.dp_total
        sub = split_grad_chunks(local, [metas[i] for i in idxs], cfg,
                                [shard_shapes[i] for i in idxs])
        for i, o in zip(idxs, sub):
            outs[i] = o
    return [o.astype(m.dtype) for o, m in zip(outs, metas)]


def reduce_group_bwd_raw(grads_full: Sequence[jax.Array],
                         metas: Sequence[ParamMeta],
                         cfg: DistConfig,
                         shard_shapes: Sequence[tuple],
                         precision: str | None = None) -> list[jax.Array]:
    """Pack grads -> one RS (reduce_dtype, mean) -> per-param local chunks."""
    ct = pack_grad_bucket(grads_full, metas, cfg)
    return finalize_grad_bucket(ct, metas, cfg, shard_shapes, precision)


# ---------------------------------------------------------------------------
# 2. The differentiable bucket gather (paper's parametrization).
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def gather_group(shards: tuple, metas: tuple, cfg: DistConfig,
                 precision: str | None = None):
    return gather_group_fwd_raw(shards, metas, cfg, precision)


def _gg_fwd(shards, metas, cfg, precision):
    outs = gather_group_fwd_raw(shards, metas, cfg, precision)
    return outs, tuple(s.shape for s in shards)


def _gg_bwd(metas, cfg, precision, shard_shapes, cts):
    # shard_shapes already carry the (1, chunk) tp-index dim where present
    grads = reduce_group_bwd_raw(cts, metas, cfg, shard_shapes, precision)
    return (tuple(grads),)


gather_group.defvjp(_gg_fwd, _gg_bwd)


# ---------------------------------------------------------------------------
# 2b. Pipe-axis param reconstruction for pipe-SHARDED single-owner groups
# (models/staging.py): a pre/post group's storage is split (S, chunk/S)
# over the pipe axis instead of zero-filled on non-owner slots, and each
# step re-assembles this device's ordinary FSDP chunk with one all-gather
# over the pipe axis.  The backward is the exact transpose: a tiled
# psum-scatter (no mean — non-consuming ranks contribute exact-zero
# cotangents by schedule masking, so the sum IS the owner's gradient, and
# each pipe rank keeps d(its slice)).
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def pipe_param_gather(x: jax.Array, axis: str, n_stages: int) -> jax.Array:
    """(..., chunk/S) pipe-local slice -> (..., chunk) full FSDP chunk."""
    return lax.all_gather(x, axis, axis=x.ndim - 1, tiled=True)


def _ppg_fwd(x, axis, n_stages):
    return lax.all_gather(x, axis, axis=x.ndim - 1, tiled=True), None


def _ppg_bwd(axis, n_stages, _res, ct):
    return (lax.psum_scatter(ct, axis, scatter_dimension=ct.ndim - 1,
                             tiled=True),)


pipe_param_gather.defvjp(_ppg_fwd, _ppg_bwd)


# ---------------------------------------------------------------------------
# 3. Per-parameter convenience (paper Fig. 1(2), group of one).
# ---------------------------------------------------------------------------
def replicate(shard: jax.Array, meta: ParamMeta, cfg: DistConfig) -> jax.Array:
    """shard -> full TP-local tensor; d(full) -> reduce-scattered d(shard)."""
    (out,) = gather_group((shard,), (meta,), cfg)
    return out


def replicate_tree(shards_tree, metas_tree, cfg: DistConfig, plan=None):
    """Gather a whole pytree of shards, bucketed per `plan` (BucketPlan) or
    per-parameter when plan is None."""
    from repro.core.bucketing import BucketPlan  # local import, no cycle

    leaves, treedef = jax.tree_util.tree_flatten(shards_tree)
    metas = treedef.flatten_up_to(metas_tree)
    if plan is None:
        groups = [[i] for i in range(len(leaves))]
        precisions = [default_precision(cfg)] * len(groups)
    else:
        assert isinstance(plan, BucketPlan)
        groups = plan.index_groups(metas_tree)
        precisions = plan.group_precisions(metas_tree, cfg)
    out: list = [None] * len(leaves)
    for grp, prec in zip(groups, precisions):
        gathered = gather_group(tuple(leaves[i] for i in grp),
                                tuple(metas[i] for i in grp), cfg, prec)
        for i, g in zip(grp, gathered):
            out[i] = g
    return jax.tree_util.tree_unflatten(treedef, out)
