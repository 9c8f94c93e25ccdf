"""Chip smoke test: SimpleFSDP trains qwen3-1.7b on a TPU through the
entry points a user calls (`Trainer` -> `parallelize` -> the SimpleFSDP
train step with gathers, backward and the fused AdamW kernel).

  python chip_smoke.py             # one chip: 6 steps at published widths
  python chip_smoke.py --chips 4   # four chips: dp=4 against the one-chip
                                   # reference, 2 steps (this phase only)

The model is qwen3-1.7b at its published widths with 7 of its 28 layers
(one chip's stage of a 4-stage pipeline deployment), random weights from a
seed and SyntheticC4 batches. The script refuses anything but a TPU: it
exits non-zero and prints no result where JAX finds none. Every check that
fails exits non-zero; the last line of standard output is the JSON
contract line {"ok": true, "device": {...}}.

One process drives every chip it uses. Checkpoints go to .chip_smoke/ in
the checkout (git-ignored) and are removed when a phase ends.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import math
import os
import re
import shutil
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "qwen3_1_7b"
LAYERS = 7            # of 28: one stage of a 4-stage pipeline
SEQ = 4096
STEPS = 6
FOUR_CHIP_STEPS = 2
# the one-chip reference holds the four-chip batch of 4 at once: at seq
# 1024 that is the one-chip phase's 4096 tokens, 13.8 GiB of a v5e's
# 15.75 as compiled
FOUR_CHIP_SEQ = 1024
# dp=4 and the one-chip reference run the same bf16 math in a different
# reduction order; a wrong gather, mean or shard shows as a factor, not a
# few per cent
RTOL = 2e-2
# Adam's first steps are sign steps of size lr on every weight. On a v5e,
# 6 steps of this model at seq 4096 ended at loss 17.9 from 12.0 with lr
# 1e-3 (oscillating), 9.3 with 3e-5 (still rising at step 3) and fell
# every step to 9.5 with 3e-6
LR = 3e-6
OUT_DIR = os.path.join(ROOT, ".chip_smoke")
GiB = 2**30


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def say(line: str) -> None:
    print(line, flush=True)


def model_config(smoke: bool = False):
    """(ArchConfig, model): published widths cut to LAYERS layers, or the
    registry's smoke config (CPU tests)."""
    from repro.models.registry import build_model, get_arch

    cfg, _ = get_arch(ARCH, smoke=smoke)
    if not smoke:
        cfg = dataclasses.replace(cfg, n_layers=LAYERS)
    return cfg, build_model(cfg)


def dist_config(data: int):
    import jax.numpy as jnp

    from repro.core.dist import DistConfig

    return DistConfig(mesh_axes=("data", "model"), mesh_shape=(data, 1),
                      param_dtype=jnp.bfloat16, reduce_dtype=jnp.float32)


def param_count(model, dcfg) -> int:
    from repro.core.meta import named_leaves
    from repro.models import runtime as RT

    metas = model.metas(dcfg)
    stacked = RT.stacked_keys(model)
    return sum(math.prod(m.global_shape) * stacked.get(k, 1)
               for k in metas for _, m in named_leaves(metas[k]))


def kernel_counts(hlo_text: str) -> collections.Counter:
    """Pallas kernels in a compiled program, by their `pallas_call` name."""
    names = collections.Counter()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        parts = op.group(1).split("/") if op else []
        # autodiff wraps the name: transpose(jvp(rmsnorm)) is rmsnorm's
        name = parts[-2] if len(parts) >= 2 else "?"
        names[re.sub(r"^(\w+\()+|\)+$", "", name)] += 1
    return names


def train_phase(model, dcfg, seq: int, batch: int, steps: int,
                ckpt_dir: str) -> dict:
    """Train `steps` steps with the Trainer as launch/train.py builds it.

    Returns per-step losses, grad norms and wall times (each step ends in
    block_until_ready), the step's compile seconds and its Pallas kernels
    by name."""
    import jax

    from repro.models.common import ShapeConfig
    from repro.optim.adamw import AdamWConfig, init_opt_state
    from repro.train.train_step import train_state_shardings
    from repro.train.trainer import Trainer, TrainerConfig

    shutil.rmtree(ckpt_dir, ignore_errors=True)
    shape = ShapeConfig("train", seq, batch, "train")
    # no warmup: the first step updates the weights too
    tcfg = TrainerConfig(ckpt_dir=ckpt_dir, total_steps=steps,
                         ckpt_every=steps, log_every=1, warmup=0)
    trainer = Trainer(model, dcfg, shape, AdamWConfig(lr=LR), tcfg)
    # compile ahead, with the shardings the trainer places the state on:
    # the first step then finds this program and runs it
    par = trainer.par
    state = (par.abstract_storage,
             jax.eval_shape(lambda s: init_opt_state(s, dcfg),
                            par.abstract_storage))
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        state, train_state_shardings(par))
    t0 = time.perf_counter()
    compiled = trainer.step_fn.lower(
        *state, model.input_specs(shape, dcfg)).compile()
    compile_s = time.perf_counter() - t0
    kernels = kernel_counts(compiled.as_text())
    del compiled
    _, _, hist = trainer.run()
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    return {"plan": trainer.plan.describe(), "compile_s": compile_s,
            "kernels": kernels,
            "loss": [h["loss"] for h in hist],
            "grad_norm": [h["grad_norm"] for h in hist],
            "step_s": [h["dt"] for h in hist]}


def one_chip(dev) -> None:
    cfg, model = model_config()
    dcfg = dist_config(1)
    say(f"model: {ARCH} d_model={cfg.d_model} heads={cfg.n_heads}/"
        f"{cfg.n_kv_heads}x{cfg.head_dim} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab} qk_norm={cfg.qk_norm} "
        f"tied={cfg.tie_embeddings}")
    say(f"reduced: n_layers 28 -> {LAYERS} (one chip's stage of a 4-stage "
        f"pipeline); assumed: random weights (seed 0), SyntheticC4 tokens, "
        f"seq {SEQ}, global batch 1, bf16 compute, f32 master weights and "
        f"grad reduction")
    say(f"params: {param_count(model, dcfg)}")
    r = train_phase(model, dcfg, SEQ, 1, STEPS,
                    os.path.join(OUT_DIR, "ckpt_1chip"))
    say(f"plan: {r['plan']}")
    later = r["step_s"][1:]
    med = statistics.median(later)
    stats = dev.memory_stats()
    peak, limit = stats["peak_bytes_in_use"], stats["bytes_limit"]
    say(f"compile_s: {r['compile_s']:.2f}")
    say(f"first_step_s: {r['step_s'][0]:.3f} (compile included)")
    say(f"step_s: median {med:.4f} over steps 2..{STEPS}: "
        + " ".join(f"{s:.4f}" for s in later))
    say(f"tokens_per_s: {SEQ / med:.0f}")
    say("loss: " + " ".join(f"{x:.4f}" for x in r["loss"]))
    say("grad_norm: " + " ".join(f"{x:.4f}" for x in r["grad_norm"]))
    say(f"peak_bytes_in_use: {peak} ({peak / GiB:.2f} GiB, "
        f"{peak / limit:.1%} of {limit / GiB:.2f} GiB)")
    say(f"tpu_custom_calls: {sum(r['kernels'].values())} "
        f"{dict(sorted(r['kernels'].items()))}")
    check(all(math.isfinite(x) for x in r["loss"] + r["grad_norm"]),
          "non-finite loss or grad norm")
    check(r["loss"][-1] < r["loss"][0], "loss did not fall")
    for k in ("rmsnorm", "adamw"):
        check(r["kernels"][k] > 0, f"no {k} kernel in the compiled step")
    check(peak >= limit / 2, "peak memory under half of HBM: not a "
          "real-size run")


def four_chips() -> None:
    import jax

    check(jax.device_count() >= 4,
          f"--chips 4 needs 4 devices, found {jax.device_count()}")
    cfg, model = model_config()
    seq = FOUR_CHIP_SEQ
    say(f"model: {cfg.name}, {cfg.n_layers} layers, seq {seq} (cut from "
        f"{SEQ} so that the reference fits one chip), global batch 4, "
        f"{FOUR_CHIP_STEPS} steps")
    dp = train_phase(model, dist_config(4), seq, 4, FOUR_CHIP_STEPS,
                     os.path.join(OUT_DIR, "ckpt_dp4"))
    peaks = [d.memory_stats()["peak_bytes_in_use"]
             for d in jax.devices()[:4]]
    say(f"dp4 plan: {dp['plan']}")
    say(f"dp4 compile_s: {dp['compile_s']:.2f}")
    say("dp4 peak_bytes_in_use: " + " ".join(str(p) for p in peaks))
    check(all(p > 0 for p in peaks), "a device of the four held no memory")
    say(f"dp4 peak max/min: {max(peaks) / min(peaks):.3f}")
    # the reference: the same model, batch and steps on device 0 alone
    ref = train_phase(model, dist_config(1), seq, 4,
                      FOUR_CHIP_STEPS, os.path.join(OUT_DIR, "ckpt_ref"))
    say(f"ref plan: {ref['plan']}")
    worst = 0.0
    for i in range(FOUR_CHIP_STEPS):
        row = []
        for k in ("loss", "grad_norm"):
            a, b = dp[k][i], ref[k][i]
            rel = abs(a - b) / abs(b)
            worst = max(worst, rel)
            row.append(f"{k} dp4 {a:.6f} ref {b:.6f} rel {rel:.2e}")
        say(f"step {i + 1}: " + "; ".join(row))
    say(f"dp4 step_s: " + " ".join(f"{s:.4f}" for s in dp["step_s"]))
    say(f"worst relative difference: {worst:.2e} (tolerance {RTOL})")
    check(all(math.isfinite(x) for k in ("loss", "grad_norm")
              for x in dp[k] + ref[k]), "non-finite loss or grad norm")
    check(worst <= RTOL, "dp=4 does not match the one-chip reference")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip dp=4 comparison")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "this smoke test runs on a TPU only", file=sys.stderr)
        return 2
    from repro.launch import jaxenv

    say(f"device: {dev.platform} {dev.device_kind} x {jax.device_count()}; "
        f"jax {jax.__version__}; compile cache "
        f"{jaxenv.init_compile_cache()}")
    if args.chips == 4:
        four_chips()
    else:
        one_chip(dev)
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
