"""Shared TP/SP-aware layer primitives (run *inside* shard_map).

Activation convention between blocks: sequence-parallel (SP) layout
``(batch_local, seq/tp, d_model)``. Each unit gathers the sequence over the
TP axis on entry and reduce-scatters partial sums back on exit — the
Megatron-SP pattern, which both halves activation memory and turns the TP
all-reduce into all-gather + reduce-scatter.

Every unit comes as a (metas, init, apply) triple over plain dicts. Params
enter `apply` already FSDP-gathered (TP-local compute tensors) — gathering is
the caller's job via core.stack / core.collectives.

TP head handling (DESIGN.md adaptation notes):
  * query heads are padded up to a multiple of tp; padded heads are hard
    masked (zero output, zero grads) via a per-rank head mask;
  * kv projections TP-shard when n_kv % tp == 0, otherwise they are
    TP-replicated — every rank computes all kv heads and slices the groups
    its local q heads need (kv-proj compute is negligible; gradients stay
    exactly correct thanks to vma's automatic replication handling).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.dist import DistConfig
from repro.core.meta import ParamMeta
from repro.core.vmautil import vary_like
from repro.models.common import ArchConfig


# ---------------------------------------------------------------------------
# SP plumbing
# ---------------------------------------------------------------------------
# NOTE: no tp_size==1 fast paths anywhere — collectives over a size-1 axis
# are free, and skipping them would leave vma (varying-manual-axes) markings
# inconsistent between single- and multi-rank meshes.
def sp_gather(x, dcfg: DistConfig):
    """(B, S/tp, D) -> (B, S, D)."""
    return lax.all_gather(x, dcfg.tp_axis, axis=1, tiled=True)


def sp_scatter(x, dcfg: DistConfig):
    """(B, S, D) partial-sums -> (B, S/tp, D) reduced."""
    return lax.psum_scatter(x, dcfg.tp_axis, scatter_dimension=1, tiled=True)


def tp_rank(dcfg: DistConfig):
    return lax.axis_index(dcfg.tp_axis)


def tp_psum(x, dcfg: DistConfig):
    return lax.psum(x, dcfg.tp_axis)


def sp_slice(x, dcfg: DistConfig):
    """Full (B, S, D) with identical values per rank -> SP (B, S/tp, D) by
    local slicing (no collective)."""
    shard = x.shape[1] // dcfg.tp_size
    return lax.dynamic_slice_in_dim(x, tp_rank(dcfg) * shard, shard, axis=1)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------
def rmsnorm(x, w, eps: float = 1e-5, unit_offset: bool = False):
    from repro.kernels.rmsnorm import ops as rms_ops
    return rms_ops.rmsnorm(x, w, eps=eps, unit_offset=unit_offset)


def norm_meta(name: str, d: int, dtype) -> ParamMeta:
    return ParamMeta(name, (d,), tp_dim=None, dtype=dtype)


def norm_init(d: int, unit_offset: bool = False):
    # gemma-style norms store (w - 1) when unit_offset; zeros either way is
    # identity for unit_offset=True, ones for standard RMSNorm.
    return jnp.zeros((d,)) if unit_offset else jnp.ones((d,))


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_cache(seq_len: int, head_dim: int, theta: float,
               positions=None, dtype=jnp.float32):
    """cos/sin tables (S, hd/2). `positions` overrides 0..S-1 (decode)."""
    if positions is None:
        positions = jnp.arange(seq_len)
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2) / head_dim))
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def apply_rope(x, cos, sin):
    """x: (B, S, H, hd); rotate-half convention."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


def rope_pos(positions, head_dim: int, theta: float, dtype=jnp.float32):
    """cos/sin for an explicit per-request position grid.

    positions: (B, S) int — each row its own offsets (ragged decode /
    chunked prefill).  Returns (B, S, hd/2) tables for `apply_rope_pos`."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2) / head_dim))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def apply_rope_pos(x, cos, sin):
    """x: (B, S, H, hd); cos/sin: (B, S, hd/2) from `rope_pos`."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention core (reference + chunked-online-softmax used for long context)
# ---------------------------------------------------------------------------
def _softcap(x, cap):
    return cap * jnp.tanh(x / cap) if cap else x


def attention_ref(q, k, v, *, causal=True, window=None, softcap=None,
                  q_scale=None):
    """q: (B, S, H, hd); k/v: (B, S, Kh, hd) with H % Kh == 0. Quadratic —
    used for seq <= ~8k; longer sequences route to attention_chunked."""
    B, S, H, hd = q.shape
    Kh = k.shape[2]
    group = H // Kh
    scale = q_scale if q_scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, S, Kh, group, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg * scale, k,
                        preferred_element_type=jnp.float32)
    scores = _softcap(scores, softcap)
    pos_q = jnp.arange(S)[:, None]
    pos_k = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((S, k.shape[1]), bool)
    if causal:
        mask &= pos_q >= pos_k
    if window is not None:
        mask &= pos_q - pos_k < window
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", p.astype(v.dtype), v)
    return out.reshape(B, S, H, hd)


def attention_chunked(q, k, v, *, causal=True, window=None, softcap=None,
                      q_scale=None, q_chunk=512, kv_chunk=1024):
    """Flash-style online-softmax attention in pure lax (the lowering used
    by dry-runs and long-context cells; the Pallas kernel in
    repro/kernels/flash_attention mirrors this blocking on real TPUs)."""
    B, S, H, hd = q.shape
    T = k.shape[1]
    Kh = k.shape[2]
    group = H // Kh
    scale = q_scale if q_scale is not None else 1.0 / math.sqrt(hd)
    nq = -(-S // q_chunk)
    nk = -(-T // kv_chunk)
    qp = jnp.pad(q, ((0, 0), (0, nq * q_chunk - S), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, nk * kv_chunk - T), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, nk * kv_chunk - T), (0, 0), (0, 0)))
    qp = qp.reshape(B, nq, q_chunk, Kh, group, hd)
    kp = kp.reshape(B, nk, kv_chunk, Kh, hd)
    vp = vp.reshape(B, nk, kv_chunk, Kh, hd)

    def per_batch(qb, kb, vb):
        # qb: (nq, qc, Kh, g, hd); kb/vb: (nk, kc, Kh, hd)
        def q_step(_, qi_idx):
            qi, iq = qi_idx
            q_pos = iq * q_chunk + jnp.arange(q_chunk)

            def kv_step(carry, inp):
                acc, m, l = carry
                kj, vj, jk = inp
                k_pos = jk * kv_chunk + jnp.arange(kv_chunk)
                s = jnp.einsum("qkgh,tkh->kgqt", qi * scale, kj,
                               preferred_element_type=jnp.float32)
                s = _softcap(s, softcap)
                msk = jnp.ones((q_chunk, kv_chunk), bool)
                if causal:
                    msk &= q_pos[:, None] >= k_pos[None, :]
                if window is not None:
                    msk &= q_pos[:, None] - k_pos[None, :] < window
                msk &= (k_pos < T)[None, :]
                s = jnp.where(msk[None, None], s, -1e30)
                m_new = jnp.maximum(m, s.max(-1))
                p = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l_new = l * corr + p.sum(-1)
                acc_new = acc * corr[..., None] + jnp.einsum(
                    "kgqt,tkh->kgqh", p, vj.astype(jnp.float32))
                return (acc_new, m_new, l_new), None

            carry0 = vary_like(
                (jnp.zeros((Kh, group, q_chunk, hd), jnp.float32),
                 jnp.full((Kh, group, q_chunk), -jnp.inf),
                 jnp.zeros((Kh, group, q_chunk))), (qi, kb, vb))
            (acc, m, l), _ = lax.scan(kv_step, carry0,
                                      (kb, vb, jnp.arange(nk)))
            out = acc / jnp.maximum(l[..., None], 1e-30)
            # (Kh, g, qc, hd) -> (qc, Kh, g, hd)
            return None, jnp.moveaxis(out, 2, 0)

        # remat per q-chunk: backward recomputes one (qc x T) row band at a
        # time instead of saving all S x T attention weights (flash-bwd
        # memory behaviour, in pure lax)
        _, outs = lax.scan(jax.checkpoint(q_step), None,
                           (qb, jnp.arange(nq)))
        return outs.reshape(nq * q_chunk, Kh * group, hd)[:S]

    out = jax.vmap(per_batch)(qp, kp, vp)
    return out.reshape(B, S, H, hd).astype(q.dtype)


def attention(q, k, v, **kw):
    """Dispatch: quadratic ref for short seq, chunked (online-softmax,
    q-chunk remat) beyond — the S x T score matrix is never live."""
    if q.shape[1] * k.shape[1] <= 1024 * 1024:
        kw.pop("q_chunk", None), kw.pop("kv_chunk", None)
        return attention_ref(q, k, v, **kw)
    return attention_chunked(q, k, v, **kw)


# ---------------------------------------------------------------------------
# Vocab-parallel embedding (+ reduce-scatter into SP)
# ---------------------------------------------------------------------------
def embed_meta(name: str, cfg: ArchConfig, dtype) -> ParamMeta:
    return ParamMeta(name, (cfg.vocab, cfg.d_model), tp_dim=0, dtype=dtype)


def embed_init(key, cfg: ArchConfig):
    return jax.random.normal(key, (cfg.vocab, cfg.d_model)) * 0.02


@jax.named_scope("embed")                  # core/obs.SCOPES
def embed_apply(table_local, ids, cfg: ArchConfig, dcfg: DistConfig,
                scale: float | None = None, scatter: bool = True):
    """table_local: (V/tp, D); ids: (B, S) -> SP (B, S/tp, D)."""
    vshard = cfg.vocab // dcfg.tp_size
    lo = tp_rank(dcfg) * vshard
    local_ids = jnp.clip(ids - lo, 0, vshard - 1)
    hit = (ids >= lo) & (ids < lo + vshard)
    x = jnp.take(table_local, local_ids, axis=0)
    x = jnp.where(hit[..., None], x, 0).astype(dcfg.param_dtype)
    if scale is not None:
        x = x * jnp.asarray(scale, dcfg.param_dtype)
    if not scatter:
        return lax.psum(x, dcfg.tp_axis)
    return sp_scatter(x, dcfg)


# ---------------------------------------------------------------------------
# Vocab-parallel LM head + fused stable cross-entropy (never materializes
# softmax over the full vocab; reductions ride psum/pmax over the TP axis).
# ---------------------------------------------------------------------------
def head_meta(name: str, cfg: ArchConfig, dtype) -> ParamMeta:
    return ParamMeta(name, (cfg.d_model, cfg.vocab), tp_dim=1, dtype=dtype)


def head_init(key, cfg: ArchConfig):
    return jax.random.normal(key, (cfg.d_model, cfg.vocab)) \
        * (0.02 / math.sqrt(2 * cfg.n_layers))


def head_logits(w_local, x, cfg: ArchConfig, dcfg: DistConfig):
    """x: (B, S, D) gathered -> local-vocab logits (B, S, V/tp), fp32."""
    logits = jnp.einsum("bsd,dv->bsv", x, w_local,
                        preferred_element_type=jnp.float32)
    return _softcap(logits, cfg.final_softcap)


def vocab_parallel_xent(logits_local, targets, valid, cfg: ArchConfig,
                        dcfg: DistConfig, z_coef: float = 0.0):
    """Stable CE over TP-sharded vocab. Returns (local mean loss, aux)."""
    vshard = cfg.vocab // dcfg.tp_size
    lo = tp_rank(dcfg) * vshard
    # the max is a numerical stabilizer only (exactly-zero gradient in
    # logsumexp); pmax has no AD rule, so compute it out-of-graph via
    # all_gather+max on a stop_gradient'ed operand.
    m_loc = lax.stop_gradient(logits_local.max(-1))
    m = lax.all_gather(m_loc, dcfg.tp_axis, axis=0, tiled=False).max(0)
    se = jnp.exp(logits_local - m[..., None]).sum(-1)
    tgt_local = jnp.clip(targets - lo, 0, vshard - 1)
    hit = (targets >= lo) & (targets < lo + vshard)
    tl = jnp.take_along_axis(logits_local, tgt_local[..., None],
                             axis=-1)[..., 0]
    tl = jnp.where(hit, tl, 0.0)
    se = lax.psum(se, dcfg.tp_axis)
    tl = lax.psum(tl, dcfg.tp_axis)
    lse = jnp.log(se) + m
    per_tok = (lse - tl) * valid
    denom = jnp.maximum(valid.sum(), 1.0)
    loss = per_tok.sum() / denom
    if z_coef:
        loss = loss + z_coef * ((lse * valid) ** 2).sum() / denom
    # SPMD gradient convention: every TP rank computes this same loss, and
    # cotangents crossing the sequence-parallel all_gather/reduce_scatter
    # transposes SUM over ranks — the differentiated objective is
    # sum_t(loss_t). Dividing by tp makes that sum the desired mean.
    # (Verified against single-device references in tests/dist_harness.py.)
    loss = loss / dcfg.tp_size
    return loss, {}


# ---------------------------------------------------------------------------
# Attention unit (one layer)
# ---------------------------------------------------------------------------
def attn_metas(cfg: ArchConfig, dcfg: DistConfig, dtype,
               prefix: str = "") -> dict:
    d, hd, tp = cfg.d_model, cfg.head_dim, dcfg.tp_size
    lay = cfg.gqa_layout(tp)
    hq, kvp = lay["hq"], lay["kvp"]
    kv_tp = 0 if lay["mode"] == "sharded" else None
    metas = {
        "wq": ParamMeta(prefix + "wq", (d, hq * hd), tp_dim=1, dtype=dtype),
        "wk": ParamMeta(prefix + "wk", (kvp * hd, d),
                        tp_dim=kv_tp, dtype=dtype),
        "wv": ParamMeta(prefix + "wv", (kvp * hd, d),
                        tp_dim=kv_tp, dtype=dtype),
        "wo": ParamMeta(prefix + "wo", (hq * hd, d), tp_dim=0, dtype=dtype),
    }
    if cfg.qk_norm:
        metas["q_norm"] = ParamMeta(prefix + "q_norm", (hd,), None, dtype)
        metas["k_norm"] = ParamMeta(prefix + "k_norm", (hd,), None, dtype)
    return metas


def attn_init(key, cfg: ArchConfig, dcfg: DistConfig) -> dict:
    d, hd, tp = cfg.d_model, cfg.head_dim, dcfg.tp_size
    lay = cfg.gqa_layout(tp)
    hq, kvp = lay["hq"], lay["kvp"]
    ks = jax.random.split(key, 4)
    sd = 0.02
    p = {
        "wq": jax.random.normal(ks[0], (d, hq * hd)) * sd,
        "wk": jax.random.normal(ks[1], (kvp * hd, d)) * sd,
        "wv": jax.random.normal(ks[2], (kvp * hd, d)) * sd,
        "wo": jax.random.normal(ks[3], (hq * hd, d))
        * (sd / math.sqrt(2 * cfg.n_layers)),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,))
        p["k_norm"] = jnp.ones((hd,))
    return p


def _local_qkv(p, xg, cfg: ArchConfig, dcfg: DistConfig):
    """Project to this rank's q heads + the kv heads they need.

    Layout from cfg.gqa_layout (mesh-independent padding): each rank's q
    heads map to a CONTIGUOUS slice of kl = max(1, kvp/tp) kv heads, so the
    decode cache stores exactly kl heads per rank (no per-q-head
    duplication). Returns q (B,S,Hl,hd), k/v (B,S,Kl,hd), head_mask (Hl,)
    zeroing padded q heads.
    """
    B, S, _ = xg.shape
    hd, tp = cfg.head_dim, dcfg.tp_size
    lay = cfg.gqa_layout(tp)
    hq_pad, kvp, g = lay["hq"], lay["kvp"], lay["g"]
    hl = hq_pad // tp
    rank = tp_rank(dcfg)

    q = jnp.einsum("bsd,dh->bsh", xg, p["wq"]).reshape(B, S, hl, hd)
    gids = rank * hl + jnp.arange(hl)
    if lay["mode"] == "sharded":
        head_mask = jnp.ones((hl,), q.dtype)
        kl = kvp // tp
        k = jnp.einsum("bsd,hd->bsh", xg, p["wk"]).reshape(B, S, kl, hd)
        v = jnp.einsum("bsd,hd->bsh", xg, p["wv"]).reshape(B, S, kl, hd)
        return q, k, v, head_mask

    # grouped: hard-mask padded q heads / dead kv groups
    head_mask = ((gids // g < cfg.n_kv_heads)
                 & (gids % g < lay["g_real"])).astype(q.dtype)
    k_all = jnp.einsum("bsd,hd->bsh", xg, p["wk"]).reshape(B, S, kvp, hd)
    v_all = jnp.einsum("bsd,hd->bsh", xg, p["wv"]).reshape(B, S, kvp, hd)
    kl = max(1, kvp // tp)
    kv_start = (rank * hl) // g
    k = lax.dynamic_slice_in_dim(k_all, kv_start, kl, axis=2)
    v = lax.dynamic_slice_in_dim(v_all, kv_start, kl, axis=2)
    return q, k, v, head_mask


def attn_apply(p, x_sp, consts, cfg: ArchConfig, dcfg: DistConfig,
               window=None, q_scale=None):
    """Full attention sublayer on SP activations (train/prefill path).

    Under context parallelism (``dcfg.cp_axis``) the SP activations are
    additionally a ZIGZAG sequence shard: RoPE phases are looked up at this
    rank's GLOBAL positions and the attention itself runs as the ctx-axis
    ring (core/context.ring_attention — KV blocks circulate, exchange
    overlapped behind per-hop compute, exact reverse-ring gradients);
    causal/sliding-window/softcap masking applies per block from global
    positions, so gemma2's local layers skip out-of-window hops."""
    xg = sp_gather(x_sp, dcfg)
    q, k, v, head_mask = _local_qkv(p, xg, cfg, dcfg)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    cos, sin = consts["rope_cos"], consts["rope_sin"]
    if dcfg.cp_size > 1:
        from repro.core import context as CX
        seq_global = xg.shape[1] * dcfg.cp_size
        pos = CX.shard_positions(dcfg, seq_global)
        cos = jnp.take(cos, pos, axis=0)
        sin = jnp.take(sin, pos, axis=0)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        out = CX.ring_attention(q, k, v, dcfg=dcfg, seq_len=seq_global,
                                causal=True, window=window,
                                softcap=cfg.attn_softcap, q_scale=q_scale)
    else:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        out = attention(q, k, v, causal=True, window=window,
                        softcap=cfg.attn_softcap, q_scale=q_scale)
    out = out * head_mask[None, None, :, None]
    B, S, hl, hd = out.shape
    o = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, hl * hd), p["wo"])
    return sp_scatter(o, dcfg)


# ---------------------------------------------------------------------------
# Gated MLP unit
# ---------------------------------------------------------------------------
def mlp_metas(cfg: ArchConfig, dcfg: DistConfig, dtype, prefix: str = "",
              d_ff: int | None = None) -> dict:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    m = {
        "wu": ParamMeta(prefix + "wu", (d, f), tp_dim=1, dtype=dtype),
        "wd": ParamMeta(prefix + "wd", (f, d), tp_dim=0, dtype=dtype),
    }
    if cfg.gated_mlp != "gelu":   # gated variants carry a gate matrix
        m["wg"] = ParamMeta(prefix + "wg", (d, f), tp_dim=1, dtype=dtype)
    return m


def mlp_init(key, cfg: ArchConfig, d_ff: int | None = None) -> dict:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    sd = 0.02
    p = {
        "wu": jax.random.normal(ks[1], (d, f)) * sd,
        "wd": jax.random.normal(ks[2], (f, d))
        * (sd / math.sqrt(2 * cfg.n_layers)),
    }
    if cfg.gated_mlp != "gelu":
        p["wg"] = jax.random.normal(ks[0], (d, f)) * sd
    return p


def mlp_apply(p, x_sp, cfg: ArchConfig, dcfg: DistConfig):
    xg = sp_gather(x_sp, dcfg)
    u = jnp.einsum("bsd,df->bsf", xg, p["wu"])
    if cfg.gated_mlp == "gelu":       # plain 2-matrix FFN
        h = jax.nn.gelu(u, approximate=True)
    else:
        g = jnp.einsum("bsd,df->bsf", xg, p["wg"])
        act = jax.nn.gelu(g, approximate=True) \
            if cfg.gated_mlp == "geglu" else jax.nn.silu(g)
        h = act * u
    o = jnp.einsum("bsf,fd->bsd", h, p["wd"])
    return sp_scatter(o, dcfg)


# ---------------------------------------------------------------------------
# Quantized KV cache (kernels/quant codec: per-128-chunk f32 scales over
# each head vector — the SAME audited path the wire collectives use, so
# cache and collective quantization cannot drift).
# ---------------------------------------------------------------------------
def kv_quantize(x, codec="int8"):
    """x: (..., hd) -> (wire values (..., hd), f32 scales (..., nc))."""
    from repro.kernels.quant import ops as QOPS
    return QOPS.encode_kv(x, codec)


def kv_dequantize(q, s, dtype):
    from repro.kernels.quant import ops as QOPS
    return QOPS.decode_kv(q, s, dtype)
