"""Quickstart: the paper's two-line user experience, in JAX.

    model = simple_fsdp(model);  model = torch.compile(model)
becomes ONE entry point:

    par = parallelize(model, dcfg, shape)       # resolves a ParallelPlan
    step = par.train_step(ocfg)                 # jit(shard_map(...))

`parallelize` works for every registered architecture and every mesh —
FSDP x TP, with ``pp_axis`` set the SAME call returns a pipelined
(GPipe/1F1B) step over per-stage SimpleFSDP storage, and with ``cp_axis``
set the sequence shards over a 'ctx' axis with ring attention
(core/context.py): pp x dp x cp x tp is a config flip, not different code.
The 4-axis mesh section below explains the axis ordering.

The original bring-your-own-module wrapper `simple_fsdp(apply_fn, params,
dcfg)` still exists as a DEPRECATED shim (second half of this file) for raw
apply functions with no model contract.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.core import DistConfig, make_mesh, parallelize, simple_fsdp


def main():
    # --- the parallelize() one-liner -------------------------------------
    from repro.data.pipeline import DataConfig, SyntheticC4, adapt_batch
    from repro.models.common import ShapeConfig
    from repro.models.registry import get_arch
    from repro.optim.adamw import AdamWConfig

    cfg, model = get_arch("qwen3_1_7b", smoke=True)
    dcfg = DistConfig(mesh_axes=("data", "model"), mesh_shape=(4, 2),
                      param_dtype=jnp.float32, storage_dtype=jnp.float32)
    # pipelining is the same call with a pipe axis, e.g.:
    #   dcfg = DistConfig(mesh_axes=("pipe", "data", "model"),
    #                     mesh_shape=(2, 2, 2), pp_axis="pipe")
    shape = ShapeConfig("train", 64, 8, "train")

    par = parallelize(model, dcfg, shape)           # frozen ParallelPlan
    print("plan:", par.plan.describe())

    # --- budgeted auto-SAC (core/memory): two lines pick the cheapest
    # per-segment remat (+offload) whose modeled peak fits the HBM budget
    par_auto = parallelize(model, dcfg.with_(remat="auto:8"), shape)
    print("auto-SAC plan:", par_auto.plan.memory.describe(),
          "->", par_auto.plan.exec_dcfg.remat)

    # --- choosing a comm precision (kernels/quant) -----------------------
    # DistConfig.comm_precision shrinks how many bytes each collective
    # moves (the planners already minimize WHEN comm happens):
    #   "bf16"   default wire dtype — BIT-exact vs the untouched path;
    #   "fp8_ag" quantize the param all-gathers only (deterministic
    #            round-to-nearest, per-128-elem-chunk fp32 scales,
    #            ~0.52x the bytes) — gradients stay full precision;
    #   "fp8"    both directions: AG as above + STOCHASTICALLY-rounded
    #            grad reduce-scatters (unbiased, no state);
    #   "fp8_ef" adds a persistent error-feedback accumulator in the
    #            optimizer state (opt_state["ef"], fp32 per shard): the
    #            residual each quantized step leaves behind is re-added
    #            to the next gradient, so the Markov-et-al. convergence
    #            guarantee applies;
    #   "auto"   per-BUCKET choice: the auto_dp planner searches
    #            partitions x {bf16, fp8_ag, fp8_ef} jointly, paying the
    #            modeled quantize/dequantize time and keeping bf16
    #            wherever comm is already hidden (ties break to bf16).
    # The wire codec is a Pallas quantize/dequantize kernel pair fused
    # into the flat-buffer pack/unpack path (kernels/quant/); run
    # `python -m benchmarks.run fig4` for the per-arch exposed-comm
    # ablation, or pytest -m quant for the parity suite.
    par_q = parallelize(model, dcfg.with_(comm_precision="auto"), shape)
    print("quant plan:", par_q.plan.describe())

    # --- picking a pipeline schedule (core/pipeline.py) ------------------
    # Four pp_schedule values: "gpipe", "1f1b", "interleaved", "zb" — and
    # "auto" (the production_dcfg default), which scores all of them by
    # modeled bubble fraction, tie-broken by in-flight activation memory,
    # and stamps the argmin into the plan (plan.pp_schedule/pp_virtual).
    # Rules of thumb behind what auto picks:
    #   * "zb" (zero-bubble W-split) beats plain 1F1B at every M: the
    #     weight-grad halves drain into the cooldown ramp at NO extra
    #     activation cost (same min(M, S-s) bound; the W queue holds
    #     parameter-GRADIENT slices instead).
    #   * "interleaved" (V virtual stage chunks per rank) shrinks the
    #     warmup/cooldown ramps ~1/V and wins on bubble when the stage
    #     slice chunks evenly (layers_per_stage % V == 0, chunkable
    #     partition) and M is small — but each rank then HOLDS ~V x the
    #     in-flight chunk states.  Under a tight remat="auto:<GB>" budget
    #     that extra in-flight memory can force a costlier remat vector
    #     than the bubble win is worth — the memory simulator models the
    #     schedule (in_flight_microbatches), so compare plan.memory.peak
    #     across explicit pp_schedule choices before overriding auto.
    #   * "gpipe" only ever matches 1f1b's bubble and holds all M
    #     microbatches — it survives as the forward-only eval path.
    # e.g.: dcfg_pp = DistConfig(mesh_axes=("pipe", "data", "model"),
    #                            mesh_shape=(2, 2, 2), pp_axis="pipe",
    #                            pp_schedule="auto")  # or "zb", or
    #                            # "interleaved" with pp_virtual=V

    # --- context parallelism (core/context.py): the 4-axis mesh ----------
    # (pipe, data, ctx, model) — each axis carries a different traffic
    # class, ordered by how much interconnect it needs:
    #   pipe  OUTERMOST: one tiny point-to-point activation send per slot
    #         (tolerates the slowest links, even DCN);
    #   data  fat FSDP all-gathers / reduce-scatters (bulk ICI bandwidth);
    #   ctx   ring-attention ppermute — one KV block per layer per hop,
    #         lighter than FSDP gathers, heavier than pipe sends, which is
    #         why ctx sits BETWEEN data and model;
    #   model INNERMOST: the highest-frequency TP psums.
    # The ctx axis shards the SEQUENCE: rows are zigzag-chunked so every
    # rank owns equal causal work, attention runs as a ring with the next
    # KV exchange overlapped behind the current chunk's compute, and the
    # ctx axis joins fsdp_axes so params shard over data x ctx (all
    # cross-ctx gradients ride explicit collectives).  Feed the step
    # zigzag-permuted batches (the Trainer does this automatically).
    from repro.core.context import zigzag_batch
    dcfg_cp = DistConfig(mesh_axes=("data", "ctx", "model"),
                         mesh_shape=(2, 2, 2), fsdp_axes=("data", "ctx"),
                         cp_axis="ctx",
                         param_dtype=jnp.float32, storage_dtype=jnp.float32)
    par_cp = parallelize(model, dcfg_cp, shape)
    print("cp plan:", par_cp.plan.describe())      # ... cp=2(ring) ...
    st_cp = par_cp.init_storage(jax.random.PRNGKey(0))
    from repro.data.pipeline import DataConfig as _DC, SyntheticC4 as _SC
    from repro.data.pipeline import adapt_batch as _ab
    b0 = _ab(_SC(_DC(vocab=cfg.vocab, seq_len=shape.seq_len,
                     global_batch=shape.global_batch)).batch(0),
             model.input_specs(shape, dcfg_cp), 0)
    loss = par_cp.loss_step(with_grads=False)(st_cp,
                                              zigzag_batch(b0, dcfg_cp))
    print(f"cp=2 ring-attention loss {float(loss):.4f} "
          f"(seq/device = {shape.seq_len // dcfg_cp.cp_size})")

    step = par.train_step(AdamWConfig(lr=1e-3))
    storage = par.init_storage(jax.random.PRNGKey(0))

    from repro.optim.adamw import init_opt_state
    opt = init_opt_state(storage)
    data = SyntheticC4(DataConfig(vocab=cfg.vocab, seq_len=shape.seq_len,
                                  global_batch=shape.global_batch))
    specs = model.input_specs(shape, dcfg)
    for i in range(5):
        batch = adapt_batch(data.batch(i), specs, step=i)
        storage, opt, metrics = step(storage, opt, batch)
        print(f"step {i} loss {float(metrics['loss']):.4f}")

    # --- serving (core/serving): plan -> prefill -> continuous decode ----
    serving_quickstart()

    # --- observability (core/obs): trace + registry + drift --------------
    observability_quickstart()

    # --- DEPRECATED: bring-your-own-module simple_fsdp shim --------------
    byo_quickstart()


def serving_quickstart():
    """Inference mirrors training: ONE frozen plan, executed by dumb loops.

    `plan_serve` is the serving analogue of `parallelize` — it freezes a
    ServePlan (page size, pool capacity, decode slots, chunked-prefill
    chunk, modeled service rates) from the hw.py roofline and the KV-arena
    byte budget.  The paged KV cache stores every sequence as fixed-size
    pages in a pooled arena (heads sharded over 'model', pages over the
    data axes) and decodes through a gather that reconstructs the dense
    logical view — so paged decode is BITWISE equal to the dense cache
    path (tests/test_serving.py asserts exact parity per family).

    When to turn the knobs:
      * prefix caching (PrefixCache): workloads with a shared system
        prompt — full prompt pages are refcounted and re-used across
        requests, so repeated prefixes prefill once;
      * int8/fp8 pages (DistConfig.kv_cache_codec="int8"/"fp8"): halves
        (or quarters) arena bytes per token via the kernels/quant codec
        (per-128-chunk scales) — more live sequences per budget, at a
        small dequant error priced by `pytest -m serving` tolerances.
    """
    import numpy as np

    from repro.core.serving import (PrefixCache, Request, dense_to_pages,
                                    plan_serve, run_virtual, synthetic_trace)
    from repro.models.common import ShapeConfig
    from repro.models.registry import get_arch
    from repro.models import runtime as RT
    from repro.train import serve as SV

    cfg, model = get_arch("qwen3_1_7b", smoke=True)
    dcfg = DistConfig(mesh_axes=("data", "model"), mesh_shape=(2, 2),
                      param_dtype=jnp.float32, reduce_dtype=jnp.float32)
    plan = plan_serve(model, dcfg, arena_bytes=64 * 2**20, max_batch=4,
                      max_seq=128, page=16)
    print(f"serve plan: page={plan.page} pool={plan.n_pages}p "
          f"slots={plan.max_batch} chunk={plan.prefill_chunk} "
          f"decode={plan.decode_step_s*1e6:.2f}us")

    # prefill once (dense), scatter into the paged arena, decode paged:
    B, prompt, gen, page = 4, 24, 8, 8
    T = prompt + gen
    max_pages, n_pages_local = T // page, (B // 2) * (T // page) + 2
    storage = RT.init_storage(model, jax.random.PRNGKey(0), dcfg)
    params = SV.serve_params_from_storage(model, storage, dcfg)
    pf, mesh = SV.make_prefill_step(model, dcfg,
                                    ShapeConfig("p", T, B, "prefill"))
    pstep, _ = SV.make_paged_step(
        model, dcfg, ShapeConfig("d", T, B, "decode"), page=page,
        n_pages_local=n_pages_local, max_pages=max_pages, mesh=mesh)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, prompt), 3,
                              cfg.vocab)
    logits, cache = pf(params, {"tokens": jnp.pad(
        toks, ((0, 0), (0, gen)), constant_values=3)})
    arena, table, pools = dense_to_pages(
        cache, np.full((B,), prompt), page, n_pages_local, max_pages,
        dp_shards=dcfg.dp_total)
    tbl = np.array(table)
    filled = -(-prompt // page)
    for b in range(B):
        for j, pid in enumerate(pools[b // (B // 2)].alloc(
                max_pages - filled)):
            tbl[b, filled + j] = pid
    table = jnp.asarray(tbl)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for i in range(gen):
        pos = jnp.full((B,), prompt + i, jnp.int32)
        lg, arena = pstep(params, arena, table, tok[:, None], pos[:, None])
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
    print(f"paged decode: {B} seqs x {gen} tokens, last ids "
          f"{np.asarray(tok).tolist()}")

    # continuous batching on the plan's virtual clock (deterministic):
    trace = synthetic_trace(16, seed=0,
                            mean_interarrival_s=plan.decode_step_s,
                            prompt_lens=(24, 48), gen_lens=(8, 16))
    m = run_virtual(plan, trace, prefix_cache=PrefixCache()).metrics()
    print(f"continuous batching: {m['requests']} reqs "
          f"{m['tok_s']:.0f} tok/s p99={m['p99_s']*1e3:.2f}ms "
          f"preempt={m['preemptions']} arena_util={m['arena_util']:.2f}")


def observability_quickstart():
    """Every cost model in the repo renders into ONE timeline and ONE
    registry (core/obs), closing the model -> measure loop:

      * `plan_trace(model, plan, shape)` walks the plan's own executed
        schedules — pooled AG/RS hiding windows, pipeline slot tables,
        ring hops, a traced serving batcher — into Chrome-trace JSON
        (open the saved file at https://ui.perfetto.dev).  The layout is
        exact: comm-lane time not covered by a compute span IS the
        planner's modeled `exposed_s` (tests assert the match within 1%).
      * `MetricsRegistry` is the typed counter/gauge/histogram sink the
        Trainer, batcher, and router all write through; JSONL snapshots
        via `TrainerConfig.metrics_jsonl` / `--metrics-jsonl`.
      * `DriftMonitor` scores measured-vs-modeled residuals per channel
        (step time, peak memory, decode rate) and names the
        worst-drifting cost model — `benchmarks/run.py obs --json` tracks
        it per arch in BENCH_obs.json.
    """
    import tempfile

    from repro.core.api import plan_parallel
    from repro.core.obs import (DriftMonitor, MetricsRegistry,
                                modeled_step_time, nonoverlapped_comm_s,
                                plan_trace)
    from repro.models.common import ShapeConfig
    from repro.models.registry import get_arch

    cfg, model = get_arch("qwen3_1_7b", smoke=True)
    dcfg = DistConfig(mesh_axes=("data", "model"),
                      mesh_shape=(jax.device_count(), 1),
                      param_dtype=jnp.float32, reduce_dtype=jnp.float32,
                      bucket_mode="auto")
    shape = ShapeConfig("t", 64, 8, "train")
    plan = plan_parallel(model, dcfg, shape)

    # one Perfetto-openable timeline of everything the plan promises
    tb = plan_trace(model, plan, shape, repeats=2, arch_cfg=cfg)
    path = tempfile.mktemp(suffix=".trace.json")
    tb.save(path)
    print(f"trace: {len(tb.events)} events -> {path} "
          f"(exposed comm {nonoverlapped_comm_s(tb.to_doc())*1e6:.1f}us)")

    # registry + drift: record a 'measured' step against the plan's promise
    reg = MetricsRegistry()
    drift = DriftMonitor(reg)
    promised = modeled_step_time(model, plan, shape)
    drift.record("step_time", promised, promised * 1.25, step=0)
    reg.gauge("train/step_time_s").set(promised * 1.25)
    print(reg.record_peak("quickstart", 2 * 2**30, 3 * 2**30))
    print(drift.report())

    # --- profile -> calibrate -> replan (core/obs/profile + calibrate) ---
    # When the drift monitor says the step-time promise is off, close the
    # loop: `profile_step` MEASURES the executed schedule (per-segment
    # compute sub-steps, per-bucket flat-buffer AG/RS, quant codec rates,
    # the wall step), `replan` re-runs every planner (bucket partition +
    # precision DP, auto:<GB> remat, microbatches, pp_schedule='auto')
    # against the calibrated stats and measured rates.  Attaching the
    # profile to plan_trace adds a PID 2 'measured' track aligned
    # span-for-span under the modeled lanes — each span carries its
    # rel_residual, so the overlay shows WHERE the model is wrong; the
    # modeled lanes themselves are untouched.  The same loop runs inside
    # the Trainer (`replan_threshold=` / --replan-threshold); trust
    # --replan-apply once the logged delta is stable across a few replans
    # — it restarts through a checkpoint, costing one save/restore +
    # recompile.
    from repro.core.obs import calibrated_step_time, profile_step, replan

    prof = profile_step(model, plan, shape, steps=1)
    new_plan, delta = replan(model, plan, shape, prof)
    resid_before = abs(promised - prof.wall_step_s) / prof.wall_step_s
    resid_after = abs(
        calibrated_step_time(model, new_plan, shape, prof)
        - prof.wall_step_s) / prof.wall_step_s
    print(f"profile: wall {prof.wall_step_s*1e3:.1f}ms, "
          f"step-time residual {resid_before:.2f} -> {resid_after:.2e} "
          f"(replan changed={delta['changed']})")
    tb2 = plan_trace(model, plan, shape, arch_cfg=cfg, profile=prof)
    path2 = tempfile.mktemp(suffix=".overlay.trace.json")
    tb2.save(path2)
    print(f"overlay trace: {len(tb2.events)} events -> {path2}")


VOCAB, D, H, SEQ, BATCH = 512, 64, 128, 32, 16


def apply_fn(params, tokens):
    """An ordinary model function written with NO distribution logic."""
    x = params["embed"][tokens]
    for blk in params["blocks"]:
        h = jnp.tanh(x @ blk["w1"] + blk["b1"])
        x = x + h @ blk["w2"]
    return x @ params["head"]


def init_params(key):
    ks = jax.random.split(key, 8)
    blocks = [
        {"w1": jax.random.normal(ks[i], (D, H)) * 0.05,
         "b1": jnp.zeros((H,)),
         "w2": jax.random.normal(ks[i + 3], (H, D)) * 0.05}
        for i in range(3)
    ]
    return {
        "embed": jax.random.normal(ks[6], (VOCAB, D)) * 0.02,
        "blocks": blocks,
        "head": jax.random.normal(ks[7], (D, VOCAB)) * 0.02,
    }


def byo_quickstart():
    """The pre-ParallelPlan API, kept as a deprecation shim: raw apply_fn +
    shaped params in, (sharded, metas, fsdp_apply) out."""
    dcfg = DistConfig(mesh_axes=("data", "model"),
                      mesh_shape=(jax.device_count(), 1),
                      param_dtype=jnp.float32, reduce_dtype=jnp.float32,
                      bucket_mode="block")
    mesh = make_mesh(dcfg)

    params = init_params(jax.random.PRNGKey(0))
    sharded, metas, fsdp_apply = simple_fsdp(apply_fn, params, dcfg)

    def step(sharded, tokens, targets):
        def loss_fn(p):
            logits = fsdp_apply(p, tokens)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            nll = -jnp.take_along_axis(logp, targets[..., None], -1)
            return nll.mean() / dcfg.tp_size
        loss, grads = jax.value_and_grad(loss_fn)(sharded)
        new = jax.tree.map(lambda p, g: p - 0.5 * g, sharded, grads)
        return lax.pmean(loss, ("data",)) * dcfg.tp_size, new

    pspecs = jax.tree.map(lambda m: m.storage_spec(dcfg), metas,
                          is_leaf=lambda x: hasattr(x, "storage_spec"))
    fn = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(pspecs, P("data"), P("data")),
        out_specs=(P(), pspecs)))

    key = jax.random.PRNGKey(1)
    for i in range(5):
        key, k1 = jax.random.split(key)
        toks = jax.random.randint(k1, (BATCH, SEQ + 1), 0, VOCAB)
        loss, sharded = fn(sharded, toks[:, :-1], toks[:, 1:])
        print(f"byo step {i} loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
