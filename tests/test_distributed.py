"""Multi-device semantics, via subprocess (8 fake CPU devices).

jax pins the device count at first init, so the main pytest process (which
must see ONE device for smoke tests) delegates to tests/dist_harness.py.
Each case asserts exact equivalence against dense single-device references —
see that module's docstring for coverage.
"""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.distributed

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(case: str, timeout: int = 540):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + ROOT
    out = subprocess.run(
        [sys.executable, "-m", "tests.dist_harness", case],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=ROOT)
    assert out.returncode == 0, \
        f"{case} failed:\n{out.stdout[-3000:]}\n{out.stderr[-3000:]}"
    assert "ALL OK" in out.stdout


def test_storage_roundtrip_multidev():
    _run("roundtrip")


def test_gather_reconstructs_params():
    _run("gather_values")


def test_vanilla_stack_matches_dense():
    """scan(remat(gather->compute)) == dense reference, all mesh layouts,
    bucketed and per-param plans."""
    _run("vanilla")


def test_remat_policies_match_dense():
    _run("remat_modes")


@pytest.mark.slow
def test_prefetch_stack_all_schedules():
    """The hand-scheduled double-buffered scan (paper's reorder+bucket)
    under every Table-6 flag combination x 3 mesh layouts."""
    _run("prefetch", timeout=560)


def test_prefetch_bucket_plans():
    _run("prefetch_buckets")


@pytest.mark.slow
def test_all_architectures_mesh_equivalence():
    """All 10 assigned archs: (2 data x 4 model) == single device, exact
    losses and gradients (TP/SP/EP/grouped-GQA paths)."""
    _run("models", timeout=560)


def test_pipeline_parallel_composability():
    """GPipe AND 1F1B over a (pipe, data, model) mesh with FSDP bucket
    gathers inside each stage — exact loss/gradient match vs the sequential
    dense model across bucket modes (paper SS4)."""
    _run("pipeline")


def test_context_parallel_ring_parity():
    """Context parallelism (core/context.py): cp2 x dp2 training — zigzag
    seq sharding + ring attention on the ctx axis — reproduces the
    cp1 x dp4 baseline exactly (losses, assembled grads, one AdamW step)
    for dense and gemma2 (window+softcap), plus the 4-axis composition
    pp2 x dp2 x cp2.  Explicit collectives only (bucket RS over data x ctx,
    reverse-ring ppermute), so exact on every jax version."""
    _run("context", timeout=560)


def test_quantized_collectives_parity():
    """Quantized collectives (kernels/quant + comm_precision): "bf16" is
    bit-exact vs the default path over two AdamW steps; fp8_ag/fp8/fp8_ef/
    auto stay within documented EF-theory tolerance with the error-feedback
    accumulator present exactly when needs_ef.  dp4 x tp1, explicit
    roundtrip before each collective, so exact on every jax version."""
    _run("quant", timeout=560)


def test_remat_vector_parity_pp2_dp2():
    """Per-segment remat policy vectors (incl. a budget-resolved
    remat='auto:<GB>' plan) == the whole-block policy, exactly, at
    pp2 x dp2 through the unified parallelize() path (core/memory)."""
    _run("remat_vector", timeout=560)


def test_trainer_pipeline_full_lm_parity():
    """The unified parallelize() path: full-LM stage partition at pp=2 vs
    the pp=1 baseline — exact losses, assembled grads, and one AdamW step
    (untied, tied/replicated-embedding, and MoE-aux archs).  tp=1, so exact
    on every jax version (explicit collectives only)."""
    _run("trainer_pipeline", timeout=560)


def test_pipeline_v2_schedules():
    """PR-6 schedules: interleaved (V=2) + zb at pp2 x dp4 == pp=1 exactly
    (losses, grads, AdamW steps) for dense + MoE, plus zamba2's uneven
    zero-padded stage partition over two chained train steps.  tp=1, so
    exact on every jax version (explicit collectives only)."""
    _run("pipeline_v2", timeout=560)


def test_serving_paged_decode_parity():
    """Serving (core/serving): paged KV decode at tp2 x dp2 — pages over
    the data axis, heads over model — is BITWISE equal to the dense-cache
    decode on the same mesh (incl. the int8 page codec), and the whole
    prefill->decode pipeline matches the tp1 x dp1 reference within the
    standard cross-mesh tolerance with identical greedy tokens.  Explicit
    collectives only, so exact on every jax version."""
    _run("serving", timeout=560)


@pytest.mark.slow
def test_trainer_pp_smoke_dense_family():
    """Every registered arch runs a pp2 x dp2 x tp2 Trainer smoke (2 steps
    + a staged checkpoint) through the ONE Trainer — dense half."""
    _run("trainer_smoke_a", timeout=560)


@pytest.mark.slow
def test_trainer_pp_smoke_moe_ssm_multimodal():
    """... and the moe/xlstm/encdec/zamba2/vlm half."""
    _run("trainer_smoke_b", timeout=560)
