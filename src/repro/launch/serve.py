"""Serving launcher CLI: prefill a synthetic batch, decode N tokens.

On an accelerator the mesh spans the real devices (default: every device
on 'model'); under JAX_PLATFORMS=cpu, --devices N (or the size of --mesh)
virtual CPU devices stand in for chips.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_1_7b --gen 16
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve \
      --arch qwen3_1_7b --smoke --mesh 2,4 --gen 16
"""

import argparse
import math

from repro.launch import jaxenv


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_1_7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--devices", type=int, default=0,
                    help="virtual CPU device count under JAX_PLATFORMS=cpu "
                         "(0 = sized to --mesh); not allowed elsewhere")
    ap.add_argument("--mesh", default=None,
                    help="'data,model' (default: every device on 'model')")
    ap.add_argument("--int8-kv", action="store_true")
    ap.add_argument("--metrics-jsonl", default=None,
                    help="append a metrics-registry snapshot (compile + "
                         "steady prefill/decode timings) here (core/obs)")
    args = ap.parse_args()

    if args.devices and not jaxenv.on_cpu():
        raise SystemExit("--devices needs JAX_PLATFORMS=cpu")
    mesh_shape = tuple(int(x) for x in args.mesh.split(",")) \
        if args.mesh else None
    jaxenv.set_cpu_device_count(
        args.devices or (math.prod(mesh_shape) if mesh_shape else 0))

    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.dist import DistConfig
    from repro.models import runtime as RT
    from repro.models.common import ShapeConfig
    from repro.models.registry import get_arch
    from repro.train import serve as SV

    print(f"compile cache: {jaxenv.init_compile_cache()}")
    mesh_shape = mesh_shape or (1, jax.device_count())
    dcfg = DistConfig(mesh_axes=("data", "model"), mesh_shape=mesh_shape,
                      param_dtype=jnp.float32, reduce_dtype=jnp.float32,
                      kv_cache_int8=args.int8_kv)
    cfg, model = get_arch(args.arch, smoke=args.smoke)
    T = args.prompt_len + args.gen
    storage = RT.init_storage(model, jax.random.PRNGKey(0), dcfg)
    params = SV.serve_params_from_storage(model, storage, dcfg)
    prefill, mesh = SV.make_prefill_step(
        model, dcfg, ShapeConfig("p", T, args.batch, "prefill"))
    decode, _ = SV.make_decode_step(
        model, dcfg, ShapeConfig("d", T, args.batch, "decode"), mesh=mesh)

    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 3, cfg.vocab)
    padded = jnp.pad(prompts, ((0, 0), (0, args.gen)), constant_values=3)

    # warm-up iteration first: the initial call pays XLA compilation, so
    # it is timed separately and kept out of the steady-state window
    t0 = time.perf_counter()
    logits, cache = prefill(params, {"tokens": padded})
    jax.block_until_ready(logits)
    t_pf_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    logits, cache = prefill(params, {"tokens": padded})
    jax.block_until_ready(logits)
    t_pf = time.perf_counter() - t0

    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    outs = [np.asarray(tok)]
    # per-request positions: every row advances independently (ragged
    # batches under continuous batching); here all start equal
    pos = jnp.full((args.batch,), args.prompt_len, jnp.int32)
    t0 = time.perf_counter()
    logits, cache = decode(params, cache, tok, pos)
    jax.block_until_ready(logits)
    t_dec_compile = time.perf_counter() - t0
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    outs.append(np.asarray(tok))
    t0 = time.perf_counter()
    for i in range(1, args.gen - 1):
        logits, cache = decode(params, cache, tok, pos + i)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        outs.append(np.asarray(tok))
    jax.block_until_ready(tok)
    t_dec = time.perf_counter() - t0
    n_steady = max(1, args.gen - 2)
    print("generated:", np.stack(outs, 1))
    print(f"compile: prefill {t_pf_compile*1e3:.1f}ms, "
          f"first-decode {t_dec_compile*1e3:.1f}ms")
    print(f"steady:  prefill {t_pf*1e3:.1f}ms; "
          f"decode {t_dec/n_steady*1e3:.1f}ms/tok; "
          f"tp={dcfg.tp_size} int8_kv={args.int8_kv}")
    if args.metrics_jsonl:
        from repro.core.obs import MetricsRegistry
        reg = MetricsRegistry()
        reg.gauge("serve/prefill_compile_s").set(t_pf_compile)
        reg.gauge("serve/decode_compile_s").set(t_dec_compile)
        reg.gauge("serve/prefill_s").set(t_pf)
        reg.gauge("serve/decode_step_s").set(t_dec / n_steady)
        reg.gauge("serve/decode_tok_s").set(
            args.batch * n_steady / max(1e-9, t_dec))
        reg.dump_jsonl(args.metrics_jsonl, arch=args.arch,
                       batch=args.batch, gen=args.gen)
        print(f"metrics: {args.metrics_jsonl}")


if __name__ == "__main__":
    main()
