"""Dense decoder-only LM family (deepseek-coder-33b, phi3-medium-14b,
gemma2-27b, qwen3-1.7b, llama3-8b, and the InternLM2 backbone of
internvl2-26b).

Variants are driven entirely by ArchConfig flags:
  * gemma2: alternating sliding-window/global attention (scanned as PAIRS so
    the stack stays homogeneous), attn/final logit softcaps, GeGLU,
    sandwich norms (pre+post), unit-offset RMSNorm, sqrt(d) embedding scale,
    tied embeddings, query_pre_attn scaling;
  * qwen3: qk-norm, tied embeddings;
  * others: llama-style RoPE + SwiGLU + GQA.

Three entry points (all run inside shard_map on local shards):
  loss_local    — training forward + vocab-parallel CE (FSDP via core.stack)
  prefill_local — serving prefill: SP forward emitting the KV cache
  decode_local  — one-token decode against the cache (TP-only weights)
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.dist import DistConfig
from repro.core.irgraph import BlockStats
from repro.core.meta import ParamMeta
from repro.core.stack import apply_stack
from repro.core import collectives as coll
from repro.core.remat import maybe_remat
from repro.models import layers as LY
from repro.models.common import (ArchConfig, BlockSegments, ShapeConfig,
                                 StageSpec, even_stage_slices)


class DenseLM:
    # Context-parallel contract (core/context.py): the dense family routes
    # attention/RoPE/loss masking through the zigzag sequence shard — the
    # whole training path is position-exact under dcfg.cp_axis.  Families
    # with their own stacks (xlstm/zamba2/encdec) or a modality stream
    # whose layout a sequence permutation would break (vlm) opt out.
    cp_supported = True

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        # gemma2 alternates (local, global); scan over pairs keeps the
        # stacked params homogeneous.
        self.layers_per_step = 2 if cfg.local_global_alternate else 1
        assert cfg.n_layers % self.layers_per_step == 0
        self.n_steps = cfg.n_layers // self.layers_per_step
        # measured BlockStats override (launch/dryrun.harvest_block_stats):
        # when set, block_stats() returns it instead of the analytic model.
        self.measured_stats: BlockStats | None = None

    # ------------------------------------------------------------- metas --
    def _sub_metas(self, dcfg: DistConfig, tag: str) -> dict:
        cfg = self.cfg
        dt = dcfg.storage_dtype
        m = {
            "ln1": LY.norm_meta(f"{tag}ln1", cfg.d_model, dt),
            "attn": LY.attn_metas(cfg, dcfg, dt, prefix=f"{tag}attn."),
            "ln2": LY.norm_meta(f"{tag}ln2", cfg.d_model, dt),
            "mlp": self._ffn_metas(dcfg, dt, prefix=f"{tag}mlp."),
        }
        if cfg.post_norms:
            m["pn1"] = LY.norm_meta(f"{tag}pn1", cfg.d_model, dt)
            m["pn2"] = LY.norm_meta(f"{tag}pn2", cfg.d_model, dt)
        return m

    def block_metas(self, dcfg: DistConfig) -> dict:
        if self.layers_per_step == 1:
            return self._sub_metas(dcfg, "")
        return {"local": self._sub_metas(dcfg, "local."),
                "global": self._sub_metas(dcfg, "global.")}

    def metas(self, dcfg: DistConfig) -> dict:
        cfg = self.cfg
        dt = dcfg.storage_dtype
        m = {
            "embed": LY.embed_meta("embed", cfg, dt),
            "blocks": self.block_metas(dcfg),
            "final_norm": LY.norm_meta("final_norm", cfg.d_model, dt),
        }
        if not cfg.tie_embeddings:
            m["head"] = LY.head_meta("head", cfg, dt)
        return m

    @property
    def stacked_keys(self) -> dict:
        """Top-level param groups carrying a leading layer-stack dim (the
        model contract consumed by models/runtime and core/api)."""
        return {"blocks": self.n_steps}

    def stage_spec(self, n_stages: int) -> StageSpec:
        """Default LM partition: embedding on stage 0, the scanned block
        stack sliced contiguously, final norm + head + loss on the last
        stage.  A tied embedding table is consumed at BOTH ends, so it is
        replicated across stages (grads psum'ed over the pipe axis)."""
        tied = self.cfg.tie_embeddings
        return StageSpec(
            n_stages=n_stages,
            pipelined="blocks",
            layers_per_stage=even_stage_slices(self.n_steps, n_stages,
                                               self.cfg.name),
            pre_keys=() if tied else ("embed",),
            post_keys=("final_norm",) + (() if tied else ("head",)),
            replicated_keys=("embed",) if tied else (),
        )

    # -------------------------------------------------------------- init --
    def _sub_init(self, key, dcfg) -> dict:
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        p = {
            "ln1": LY.norm_init(cfg.d_model, cfg.post_norms),
            "attn": LY.attn_init(k1, cfg, dcfg),
            "ln2": LY.norm_init(cfg.d_model, cfg.post_norms),
            "mlp": self._ffn_init(k2, dcfg),
        }
        if cfg.post_norms:
            p["pn1"] = LY.norm_init(cfg.d_model, True)
            p["pn2"] = LY.norm_init(cfg.d_model, True)
        return p

    def init_block_full(self, key, dcfg) -> dict:
        if self.layers_per_step == 1:
            return self._sub_init(key, dcfg)
        k1, k2 = jax.random.split(key)
        return {"local": self._sub_init(k1, dcfg),
                "global": self._sub_init(k2, dcfg)}

    def init_full(self, key, dcfg: DistConfig) -> dict:
        """Full shaped params (host-side; small/smoke configs only)."""
        cfg = self.cfg
        keys = jax.random.split(key, self.n_steps + 2)
        blocks = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[self.init_block_full(keys[i], dcfg) for i in range(self.n_steps)]
        )
        p = {
            "embed": LY.embed_init(keys[-1], cfg),
            "blocks": blocks,
            "final_norm": LY.norm_init(cfg.d_model, cfg.post_norms),
        }
        if not cfg.tie_embeddings:
            p["head"] = LY.head_init(keys[-2], cfg)
        return p

    # --------------------------------------------------------- constants --
    def consts(self, seq_len: int, dcfg: DistConfig, positions=None) -> dict:
        cos, sin = LY.rope_cache(seq_len, self.cfg.head_dim,
                                 self.cfg.rope_theta, positions=positions)
        return {"rope_cos": cos, "rope_sin": sin}

    # ------------------------------------------------------------- block --
    @property
    def _q_scale(self):
        cfg = self.cfg
        if cfg.name.startswith("gemma2"):
            return 256.0 ** -0.5      # query_pre_attn_scalar
        return 1.0 / math.sqrt(cfg.head_dim)

    # FFN hooks — overridden by the MoE family --------------------------------
    def _ffn_metas(self, dcfg, dtype, prefix=""):
        return LY.mlp_metas(self.cfg, dcfg, dtype, prefix=prefix)

    def _ffn_init(self, key, dcfg):
        return LY.mlp_init(key, self.cfg)

    def _ffn_apply(self, p, x_sp, dcfg):
        return LY.mlp_apply(p, x_sp, self.cfg, dcfg), {}

    def _ffn_decode(self, p, x, dcfg):
        cfg = self.cfg
        hg = jnp.einsum("bsd,df->bsf", x, p["wg"])
        hu = jnp.einsum("bsd,df->bsf", x, p["wu"])
        act = jax.nn.gelu(hg, approximate=True) \
            if cfg.gated_mlp == "geglu" else jax.nn.silu(hg)
        o = jnp.einsum("bsf,fd->bsd", act * hu, p["wd"])
        o = lax.psum(o, dcfg.tp_axis)
        return o

    @jax.named_scope("attn")               # core/obs.SCOPES
    def _attn_half(self, p, consts, x, dcfg, window):
        """Attention residual branch: consumes ln1 + attn.* (+pn1)."""
        cfg = self.cfg
        uo = cfg.post_norms  # gemma-style unit-offset norms
        h = LY.rmsnorm(x, p["ln1"], cfg.norm_eps, uo)
        h = LY.attn_apply(p["attn"], h, consts, cfg, dcfg, window=window,
                          q_scale=self._q_scale)
        if cfg.post_norms:
            h = LY.rmsnorm(h, p["pn1"], cfg.norm_eps, uo)
        return x + h

    @jax.named_scope("mlp")                # core/obs.SCOPES
    def _mlp_half(self, p, consts, x, dcfg):
        """FFN residual branch: consumes ln2 + mlp.* (+pn2); returns aux."""
        cfg = self.cfg
        uo = cfg.post_norms
        h = LY.rmsnorm(x, p["ln2"], cfg.norm_eps, uo)
        h, aux = self._ffn_apply(p["mlp"], h, dcfg)
        if cfg.post_norms:
            h = LY.rmsnorm(h, p["pn2"], cfg.norm_eps, uo)
        return x + h, aux

    def _sub_block(self, p, consts, x, dcfg, window):
        x = self._attn_half(p, consts, x, dcfg, window)
        return self._mlp_half(p, consts, x, dcfg)

    def block_fn(self, p, consts, x, dcfg: DistConfig):
        cfg = self.cfg
        if self.layers_per_step == 1:
            w = cfg.sliding_window if not cfg.local_global_alternate else None
            y, aux = self._sub_block(p, consts, x, dcfg, w)
            return y, aux
        # remat each half of the pair: halves peak backward residency
        sub = jax.checkpoint(
            lambda pp, xx, w: self._sub_block(pp, consts, xx, dcfg, w),
            static_argnums=(2,))
        x, aux1 = sub(p["local"], x, cfg.sliding_window)
        x, aux2 = sub(p["global"], x, None)
        return x, jax.tree.map(jnp.add, aux1, aux2)

    def block_segments(self, dcfg: DistConfig) -> BlockSegments:
        """Segmented block contract (attn / mlp residual branches).

        Each segment consumes exactly the params its globs name, so the
        prefetch stack can overlap the mlp bucket's all-gather with the attn
        segment's compute (and layer i+1's attn bucket with the mlp
        segment). The gemma2 local/global pair yields four segments; aux
        from the local mlp rides the inter-segment state.
        """
        cfg = self.cfg
        if self.layers_per_step == 1:
            w = cfg.sliding_window if not cfg.local_global_alternate else None

            def seg_attn(p, consts, x):
                return self._attn_half(p, consts, x, dcfg, w)

            def seg_mlp(p, consts, x):
                return self._mlp_half(p, consts, x, dcfg)

            return BlockSegments(
                names=("attn", "mlp"),
                param_globs=(("ln1", "attn/*", "pn1"),
                             ("ln2", "mlp/*", "pn2")),
                fns=(seg_attn, seg_mlp))

        def l_attn(p, consts, x):
            return self._attn_half(p["local"], consts, x, dcfg,
                                   cfg.sliding_window)

        def l_mlp(p, consts, x):
            return self._mlp_half(p["local"], consts, x, dcfg)

        def g_attn(p, consts, st):
            x, aux = st
            return self._attn_half(p["global"], consts, x, dcfg, None), aux

        def g_mlp(p, consts, st):
            x, aux = st
            y, aux2 = self._mlp_half(p["global"], consts, x, dcfg)
            return y, jax.tree.map(jnp.add, aux, aux2)

        # checkpoint each pair segment: block_fn remats each half to halve
        # peak backward residency, and the segmented path must not hold all
        # four segments' un-rematted vjp residuals at once — with checkpoint
        # the per-segment residuals are just the inter-segment states.
        return BlockSegments(
            names=("local.attn", "local.mlp", "global.attn", "global.mlp"),
            param_globs=(("local/ln1", "local/attn/*", "local/pn1"),
                         ("local/ln2", "local/mlp/*", "local/pn2"),
                         ("global/ln1", "global/attn/*", "global/pn1"),
                         ("global/ln2", "global/mlp/*", "global/pn2")),
            fns=tuple(jax.checkpoint(f)
                      for f in (l_attn, l_mlp, g_attn, g_mlp)))

    # ------------------------------------------------------------- train --
    def _embed_in(self, storage, tokens, dcfg):
        cfg = self.cfg
        emb_meta = LY.embed_meta("embed", cfg, dcfg.storage_dtype)

        def embed_fn(emb_shard, ids):
            table = coll.replicate(emb_shard, emb_meta, dcfg)
            scale = math.sqrt(cfg.d_model) if cfg.post_norms else None
            return LY.embed_apply(table, ids, cfg, dcfg, scale=scale)

        return maybe_remat(embed_fn, "fsdp_only" if dcfg.remat != "none"
                           else "none")(storage["embed"], tokens)

    def _lm_head(self, storage, x_sp, dcfg):
        cfg = self.cfg
        x = LY.sp_gather(x_sp, dcfg)
        if cfg.tie_embeddings:
            emb_meta = LY.embed_meta("embed", cfg, dcfg.storage_dtype)
            table = coll.replicate(storage["embed"], emb_meta, dcfg)
            logits = jnp.einsum("bsd,vd->bsv", x, table,
                                preferred_element_type=jnp.float32)
            logits = LY._softcap(logits, cfg.final_softcap)
        else:
            head_meta = LY.head_meta("head", cfg, dcfg.storage_dtype)
            w = coll.replicate(storage["head"], head_meta, dcfg)
            logits = LY.head_logits(w, x, cfg, dcfg)
        return logits

    def _aux0(self) -> dict:
        """Zero-valued aux accumulator matching apply_stack's aux structure
        (part of the inter-stage pipeline state)."""
        return {}

    def _loss_aux(self, aux):
        """Scalar added to the CE loss from the accumulated aux (MoE)."""
        return 0.0

    # -- the stage-partition contract (models/common.StageSpec). The three
    # methods compose to loss_local at pp=1 and are driven per-stage by the
    # pipeline schedules under dcfg.pp_axis; the inter-stage state is
    # (x_sp, aux_sums).
    def stage_pre(self, storage, mb, dcfg: DistConfig):
        """Stage-0 entry: tokens -> SP-layout embeddings (+ zero aux)."""
        return self._embed_in(storage, mb["tokens"], dcfg), self._aux0()

    def stage_blocks(self, storage, state, dcfg: DistConfig, plan=None):
        """This stage's contiguous slice of the scanned block stack."""
        x, aux = state
        # S_local is the per-device (cp-shard) sequence; RoPE tables span
        # the GLOBAL sequence and attn_apply slices them at this rank's
        # zigzag positions.  Planner stats describe per-device work.
        B, S_local = x.shape[0], x.shape[1] * dcfg.tp_size
        consts = self.consts(S_local * dcfg.cp_size, dcfg)
        blk = functools.partial(self.block_fn, dcfg=dcfg)
        x, aux2 = apply_stack(blk, self.block_metas(dcfg), dcfg,
                              storage["blocks"], consts, x, plan=plan,
                              block_stats=self.block_stats(dcfg,
                                                           (B, S_local)),
                              segments=self.block_segments(dcfg))
        return x, jax.tree.map(jnp.add, aux, aux2)

    @jax.named_scope("head_loss")          # core/obs.SCOPES
    def stage_loss(self, storage, state, mb, dcfg: DistConfig):
        """Last-stage exit: final norm, LM head, vocab-parallel CE (+aux)."""
        cfg = self.cfg
        x, aux = state
        fn_meta = LY.norm_meta("final_norm", cfg.d_model, dcfg.storage_dtype)
        w_fn = coll.replicate(storage["final_norm"], fn_meta, dcfg)
        x = LY.rmsnorm(x, w_fn, cfg.norm_eps, cfg.post_norms)
        logits = self._lm_head(storage, x, dcfg)
        loss, _ = LY.vocab_parallel_xent(
            logits, mb["targets"], mb["valid"], cfg, dcfg)
        return loss + self._loss_aux(aux)

    def loss_local(self, storage, batch, dcfg: DistConfig):
        """batch: tokens/targets (B,S) int32, valid (B,S) f32. Local mean."""
        state = self.stage_blocks(storage,
                                  self.stage_pre(storage, batch, dcfg), dcfg)
        return self.stage_loss(storage, state, batch, dcfg), state[1]

    # ------------------------------------------------------------- serve --
    def serve_block_metas(self, dcfg: DistConfig) -> dict:
        return self.block_metas(dcfg)

    def _serve_sub(self, p, consts, x_sp, dcfg, window):
        """Prefill sublayer: like _sub_block but also returns (k, v)."""
        cfg = self.cfg
        uo = cfg.post_norms
        h = LY.rmsnorm(x_sp, p["ln1"], cfg.norm_eps, uo)
        xg = LY.sp_gather(h, dcfg)
        q, k, v, head_mask = LY._local_qkv(p["attn"], xg, cfg, dcfg)
        if cfg.qk_norm:
            q = LY.rmsnorm(q, p["attn"]["q_norm"], cfg.norm_eps)
            k = LY.rmsnorm(k, p["attn"]["k_norm"], cfg.norm_eps)
        cos, sin = consts["rope_cos"], consts["rope_sin"]
        q = LY.apply_rope(q, cos, sin)
        k = LY.apply_rope(k, cos, sin)
        out = LY.attention(q, k, v, causal=True, window=window,
                           softcap=cfg.attn_softcap, q_scale=self._q_scale)
        out = out * head_mask[None, None, :, None]
        B, S, hl, hd = out.shape
        o = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, hl * hd),
                       p["attn"]["wo"])
        h = LY.sp_scatter(o, dcfg)
        if cfg.post_norms:
            h = LY.rmsnorm(h, p["pn1"], cfg.norm_eps, uo)
        x = x_sp + h
        h = LY.rmsnorm(x, p["ln2"], cfg.norm_eps, uo)
        h, _ = self._ffn_apply(p["mlp"], h, dcfg)
        if cfg.post_norms:
            h = LY.rmsnorm(h, p["pn2"], cfg.norm_eps, uo)
        codec = dcfg.kv_codec
        if codec:
            kq, ks = LY.kv_quantize(k, codec)
            vq, vs = LY.kv_quantize(v, codec)
            return x + h, {"k": kq, "ks": ks, "v": vq, "vs": vs}
        return x + h, (k.astype(dcfg.param_dtype), v.astype(dcfg.param_dtype))

    def prefill_block(self, p, consts, x, dcfg):
        cfg = self.cfg
        if self.layers_per_step == 1:
            w = cfg.sliding_window if not cfg.local_global_alternate else None
            y, kv = self._serve_sub(p, consts, x, dcfg, w)
            return y, kv
        y, kv_l = self._serve_sub(p["local"], consts, x, dcfg,
                                  cfg.sliding_window)
        y, kv_g = self._serve_sub(p["global"], consts, y, dcfg, None)
        return y, (kv_l, kv_g)

    def prefill_local(self, params_tp, batch, dcfg: DistConfig):
        """params_tp: TP-local FULL params stacked (n_steps, ...).

        Returns (last-token logits (B, V/tp), kv cache pytree stacked)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        consts = self.consts(tokens.shape[1], dcfg)
        scale = math.sqrt(cfg.d_model) if cfg.post_norms else None
        x = LY.embed_apply(params_tp["embed"], tokens, cfg, dcfg, scale=scale)

        def body(xc, p):
            y, kv = self.prefill_block(p, consts, xc, dcfg)
            return y, kv

        x, cache = lax.scan(body, x, params_tp["blocks"])
        x = LY.rmsnorm(x, params_tp["final_norm"], cfg.norm_eps,
                       cfg.post_norms)
        xg = LY.sp_gather(x, dcfg)[:, -1:]
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", xg, params_tp["embed"],
                                preferred_element_type=jnp.float32)
        else:
            logits = jnp.einsum("bsd,dv->bsv", xg, params_tp["head"],
                                preferred_element_type=jnp.float32)
        logits = LY._softcap(logits, cfg.final_softcap)
        return logits[:, 0], cache

    # decode -----------------------------------------------------------------
    # Paged-serving contract (core/serving): this family stores its cache
    # as fixed-size KV pages in a pooled arena and decodes through the
    # gather/scatter path below.  Recurrent families (xlstm/zamba2) carry
    # O(1) state — paging does not apply; encdec's dual cache is a
    # follow-up (ROADMAP serving notes).
    paged_kv = True

    def _dense_writer(self, kv, k, v, *, qpos, dcfg):
        """Commit new (B,C,Kl,hd) K/V into the dense (B,T,...) cache at
        per-request positions qpos (B,C); returns (new_kv, ck, cv) where
        ck/cv are the full dense read views the attention consumes."""
        ib = jnp.arange(k.shape[0])[:, None]
        codec = dcfg.kv_codec
        if codec:
            kq, ks = LY.kv_quantize(k, codec)
            vq, vs = LY.kv_quantize(v, codec)
            kv = {
                "k": kv["k"].at[ib, qpos].set(kq),
                "ks": kv["ks"].at[ib, qpos].set(ks),
                "v": kv["v"].at[ib, qpos].set(vq),
                "vs": kv["vs"].at[ib, qpos].set(vs),
            }
            ck = LY.kv_dequantize(kv["k"], kv["ks"], dcfg.param_dtype)
            cv = LY.kv_dequantize(kv["v"], kv["vs"], dcfg.param_dtype)
            return kv, ck, cv
        ck, cv = kv
        ck = ck.at[ib, qpos].set(k.astype(ck.dtype))
        cv = cv.at[ib, qpos].set(v.astype(cv.dtype))
        return (ck, cv), ck, cv

    def _paged_writer(self, kv, k, v, *, table, qpos, dcfg, page):
        """Paged cache commit: scatter new K/V into the page pool at the
        slots `table` maps qpos to, then gather the table's full logical
        window back as the dense read views (exactly the dense cache
        contents for every allocated position <= qpos)."""
        from repro.core.serving import pages as PG
        codec = dcfg.kv_codec
        if codec:
            kq, ks = LY.kv_quantize(k, codec)
            vq, vs = LY.kv_quantize(v, codec)
            kv = {
                "k": PG.scatter_tokens(kv["k"], table, qpos, kq, page),
                "ks": PG.scatter_tokens(kv["ks"], table, qpos, ks, page),
                "v": PG.scatter_tokens(kv["v"], table, qpos, vq, page),
                "vs": PG.scatter_tokens(kv["vs"], table, qpos, vs, page),
            }
            ck = LY.kv_dequantize(PG.gather_tokens(kv["k"], table, page),
                                  PG.gather_tokens(kv["ks"], table, page),
                                  dcfg.param_dtype)
            cv = LY.kv_dequantize(PG.gather_tokens(kv["v"], table, page),
                                  PG.gather_tokens(kv["vs"], table, page),
                                  dcfg.param_dtype)
            return kv, ck, cv
        pk, pv = kv
        pk = PG.scatter_tokens(pk, table, qpos, k.astype(pk.dtype), page)
        pv = PG.scatter_tokens(pv, table, qpos, v.astype(pv.dtype), page)
        return ((pk, pv), PG.gather_tokens(pk, table, page),
                PG.gather_tokens(pv, table, page))

    def _decode_sub(self, p, x, kv, qpos, cos, sin, dcfg, window,
                    writer=None):
        """x: (B,C,D) replicated over model; qpos: (B,C) absolute
        positions per query token.  `writer(kv, k, v)` commits new K/V to
        the cache and returns (new_kv, ck, cv) dense read views
        (B,T,Kl,hd); the default writes the dense cache in place."""
        cfg = self.cfg
        uo = cfg.post_norms
        h = LY.rmsnorm(x, p["ln1"], cfg.norm_eps, uo)
        q, k, v, head_mask = LY._local_qkv(p["attn"], h, cfg, dcfg)
        if cfg.qk_norm:
            q = LY.rmsnorm(q, p["attn"]["q_norm"], cfg.norm_eps)
            k = LY.rmsnorm(k, p["attn"]["k_norm"], cfg.norm_eps)
        q = LY.apply_rope_pos(q, cos, sin)
        k = LY.apply_rope_pos(k, cos, sin)
        if writer is None:
            writer = functools.partial(self._dense_writer, qpos=qpos,
                                       dcfg=dcfg)
        new_kv, ck, cv = writer(kv, k, v)
        B, C = qpos.shape
        T = ck.shape[1]
        kl = ck.shape[2]
        hl = q.shape[2]
        group = hl // kl
        qg = q.reshape(B, C, kl, group, cfg.head_dim)
        s = jnp.einsum("bqkgh,btkh->bkgqt", qg * self._q_scale, ck,
                       preferred_element_type=jnp.float32)
        s = LY._softcap(s, cfg.attn_softcap)
        tpos = jnp.arange(T)
        msk = tpos[None, None, :] <= qpos[:, :, None]
        if window is not None:
            msk &= tpos[None, None, :] > qpos[:, :, None] - window
        s = jnp.where(msk[:, None, None, :, :], s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgqt,btkh->bqkgh", pr.astype(cv.dtype), cv)
        out = out.reshape(B, C, hl, cfg.head_dim)
        out = out * head_mask[None, None, :, None]
        o = jnp.einsum("bsh,hd->bsd",
                       out.reshape(B, C, hl * cfg.head_dim),
                       p["attn"]["wo"])
        o = lax.psum(o, dcfg.tp_axis)
        if cfg.post_norms:
            o = LY.rmsnorm(o, p["pn1"], cfg.norm_eps, uo)
        x = x + o
        h = LY.rmsnorm(x, p["ln2"], cfg.norm_eps, uo)
        o = self._ffn_decode(p["mlp"], h, dcfg)
        if cfg.post_norms:
            o = LY.rmsnorm(o, p["pn2"], cfg.norm_eps, uo)
        return x + o, new_kv

    def _cached_forward(self, params_tp, cache, toks, qpos, dcfg,
                        writer=None):
        """Shared decode/chunked-prefill core: embed toks (B,C) at
        positions qpos (B,C), scan the stack against the cache (dense or
        paged via `writer`), return (last-position logits, cache)."""
        cfg = self.cfg
        cos, sin = LY.rope_pos(qpos, cfg.head_dim, cfg.rope_theta)
        scale = math.sqrt(cfg.d_model) if cfg.post_norms else None
        x = LY.embed_apply(params_tp["embed"], toks, cfg, dcfg, scale=scale,
                           scatter=False)

        # The cache rides the scan CARRY and is updated in place at the
        # layer index: XLA aliases in-place dynamic-update-slice on while
        # carries, so exactly ONE cache buffer is ever live (scan xs/ys
        # emission would double-buffer it).
        L = self.n_steps

        def slice_kv(kv, idx):
            return jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, idx, 0,
                                                   keepdims=False), kv)

        def put_kv(kv, new, idx):
            return jax.tree.map(
                lambda a, n: lax.dynamic_update_index_in_dim(
                    a, n.astype(a.dtype), idx, 0), kv, new)

        def body(carry, inputs):
            xc, cache_all = carry
            p, idx = inputs
            kv = slice_kv(cache_all, idx)
            if self.layers_per_step == 1:
                w = cfg.sliding_window \
                    if not cfg.local_global_alternate else None
                y, kv2 = self._decode_sub(p, xc, kv, qpos, cos, sin, dcfg,
                                          w, writer)
            else:
                y, kv_l = self._decode_sub(p["local"], xc, kv[0], qpos,
                                           cos, sin, dcfg,
                                           cfg.sliding_window, writer)
                y, kv_g = self._decode_sub(p["global"], y, kv[1], qpos,
                                           cos, sin, dcfg, None, writer)
                kv2 = (kv_l, kv_g)
            return (y, put_kv(cache_all, kv2, idx)), None

        (x, cache), _ = lax.scan(
            body, (x, cache), (params_tp["blocks"], jnp.arange(L)))
        x = LY.rmsnorm(x, params_tp["final_norm"], cfg.norm_eps,
                       cfg.post_norms)
        x = x[:, -1:]
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x, params_tp["embed"],
                                preferred_element_type=jnp.float32)
        else:
            logits = jnp.einsum("bsd,dv->bsv", x, params_tp["head"],
                                preferred_element_type=jnp.float32)
        logits = LY._softcap(logits, cfg.final_softcap)
        return logits[:, 0], cache

    def decode_local(self, params_tp, cache, tok, pos, dcfg: DistConfig):
        """One decode step. tok: (B,) int32; pos: (B,) int32 PER-REQUEST
        positions — ragged batches decode at their own offsets.
        cache: pytree of (n_steps, B, T, Kl, hd) pairs."""
        return self._cached_forward(params_tp, cache, tok[:, None],
                                    pos[:, None], dcfg)

    def paged_step_local(self, params_tp, arena, table, toks, qpos, dcfg,
                         page: int):
        """One paged serving step: decode (C=1) or a prefill chunk (C>1).

        arena: pytree of page pools, leaves (n_steps, n_pages+1, page, ...)
        — the last pool row is the scratch page that inactive slots
        (table entries -1) harmlessly write to; table: (B, max_pages)
        int32 page ids local to this shard's pool; toks/qpos: (B, C).
        Returns (last-position logits (B, V/tp), updated arena)."""
        writer = functools.partial(self._paged_writer, table=table,
                                   qpos=qpos, dcfg=dcfg, page=page)
        return self._cached_forward(params_tp, arena, toks, qpos, dcfg,
                                    writer=writer)

    # ----------------------------------------------------------- costing --
    def block_stats(self, dcfg: DistConfig, batch_shape) -> BlockStats:
        """Per-(scan-step) workload for auto-wrapping, per device.

        Analytic (hw.py roofline) by default; when the dryrun harvested
        measured costs for this model instance (`measured_stats`, keyed by
        the same param names and shaped at the cell's own microbatch) those
        replace the analytic numbers."""
        if self.measured_stats is not None:
            return self.measured_stats
        cfg = self.cfg
        B, S = batch_shape          # per-device microbatch (cp-local seq)
        tokens = B * S
        d, hd = cfg.d_model, cfg.head_dim
        hq = cfg.q_heads_padded(dcfg.tp_size)
        pf, pb = {}, {}
        it = jnp.dtype(dcfg.param_dtype).itemsize

        def add(name, flops, nbytes):
            pf[name] = flops
            pb[name] = nbytes

        names, metas, _ = [], [], None
        from repro.core.meta import named_leaves
        for nm, m in named_leaves(self.block_metas(dcfg)):
            numel = m.numel_local(dcfg)
            # matmul params: 2*tokens*numel flops; norms: O(tokens*d)
            flops = 2.0 * tokens * numel if numel > 4 * d \
                else 8.0 * tokens * d / max(1, dcfg.tp_size)
            add(nm, flops, numel * it + flops / max(d, 1) * it)
        # attention itself (not a param op) folds into wq's consumer cost.
        # Under context parallelism each rank's S/cp queries attend to the
        # FULL sequence (the ring visits every KV block), so the kv span is
        # S * cp — this is what lets the bucket planners re-tighten when
        # per-device matmul compute shrinks by cp.
        attn_flops = 4.0 * tokens * (S * dcfg.cp_size) * hd \
            * (hq / dcfg.tp_size)
        first = next(iter(pf))
        pf[first] += attn_flops
        act = tokens * d * it / dcfg.tp_size
        return BlockStats(param_flops=pf, param_bytes=pb, act_bytes=act)

    def bucket_units(self) -> list[list[str]]:
        """Manual-wrapping module lists (paper: per-transformer-block)."""
        if self.layers_per_step == 2:
            return [["local/*"], ["global/*"]]
        return [["attn/*", "ln1"], ["mlp/*", "ln2", "pn1", "pn2"]]

    # ------------------------------------------------------------ inputs --
    def input_specs(self, shape: ShapeConfig, dcfg: DistConfig) -> dict:
        B, S = shape.global_batch, shape.seq_len
        ids = jax.ShapeDtypeStruct((B, S), jnp.int32)
        if shape.kind == "train":
            return {"tokens": ids, "targets": ids,
                    "valid": jax.ShapeDtypeStruct((B, S), jnp.float32)}
        if shape.kind == "prefill":
            return {"tokens": ids}
        # decode: one token + cache handled by launch/serve
        return {"tok": jax.ShapeDtypeStruct((B,), jnp.int32)}
