"""Benchmark harness entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. A CPU planner tool: distributed
behaviour benches run on 8 virtual CPU devices (set here, in this entry
point only — tests and the dry-run manage their own device counts), even
where a chip is attached. No number it prints is a device measurement.

Run:  PYTHONPATH=src python -m benchmarks.run [table3 table5 ...] [--json]

``--json`` additionally writes machine-readable results for the benches that
support it (fig4 -> benchmarks/results/BENCH_overlap.json: per-arch exposure
+ modeled step time for the none/block/greedy/auto_dp plans; pipeline ->
benchmarks/results/BENCH_pipeline.json: modeled bubble fraction + per-stage
exposure per schedule over the staged archs; mem ->
benchmarks/results/BENCH_memory.json: modeled per-device peak + step time
per remat mode per arch incl. the budgeted auto-SAC row — the paper's
Table 3 sweep; ctx -> benchmarks/results/BENCH_context.json: per ctx
degree, the per-device sequence shard, modeled ring exposure and modeled
peak/activation memory — the long-context sweep; serve ->
benchmarks/results/BENCH_serving.json: ServePlan analytics — modeled paged
vs dense decode tok/s, continuous-vs-static virtual-clock latency, prefix
hit rates; obs -> benchmarks/results/BENCH_obs.json: instrumentation
overhead of the metrics registry vs a smoke step, per-arch
modeled-vs-measured drift residuals for step time / peak memory / decode
rate, and the trace invariant — non-overlapped comm-lane time equals the
modeled exposed_s on the pp2 x dp2 x cp2 layout; profile ->
benchmarks/results/BENCH_profile.json: the closed profile -> calibrate ->
replan loop per arch — measured wall step, the analytic plan's
modeled-step residual vs the calibrated replanned plan's (the calibrated
|residual| must be strictly smaller), plus the modeled-vs-measured
overlay trace invariant) so the perf trajectory is tracked across PRs.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "src"))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RESULTS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results")
OVERLAP_JSON = os.path.join(RESULTS_DIR, "BENCH_overlap.json")
PIPELINE_JSON = os.path.join(RESULTS_DIR, "BENCH_pipeline.json")
MEMORY_JSON = os.path.join(RESULTS_DIR, "BENCH_memory.json")
CONTEXT_JSON = os.path.join(RESULTS_DIR, "BENCH_context.json")
SERVING_JSON = os.path.join(RESULTS_DIR, "BENCH_serving.json")
OBS_JSON = os.path.join(RESULTS_DIR, "BENCH_obs.json")
PROFILE_JSON = os.path.join(RESULTS_DIR, "BENCH_profile.json")


def main() -> None:
    from benchmarks import paper_tables as T
    from benchmarks import roofline

    args = sys.argv[1:]
    flags = [a for a in args if a.startswith("--")]
    unknown = [f for f in flags if f != "--json"]
    if unknown:
        sys.exit(f"unknown flag(s): {unknown}; supported: --json")
    emit_json = "--json" in flags
    names = [a for a in args if not a.startswith("--")]

    benches = {
        "table3": T.table3_debuggability,
        "table4": T.table4_compile_time,
        "table5": T.table5_reorder_bucket,
        "table6": T.table6_ag_placement,
        "fig3": T.fig3_vs_gspmd,
        "fig4": lambda: T.fig4_autowrap(
            json_path=OVERLAP_JSON if emit_json else None),
        "fig5": T.fig5_convergence,
        "pipeline": lambda: T.pipeline_bench(
            json_path=PIPELINE_JSON if emit_json else None),
        "mem": lambda: T.memory_table(
            json_path=MEMORY_JSON if emit_json else None),
        "ctx": lambda: T.context_table(
            json_path=CONTEXT_JSON if emit_json else None),
        "serve": lambda: T.serving_table(
            json_path=SERVING_JSON if emit_json else None),
        "obs": lambda: T.obs_table(
            json_path=OBS_JSON if emit_json else None),
        "profile": lambda: T.profile_table(
            json_path=PROFILE_JSON if emit_json else None),
        "roofline": lambda: roofline.emit_csv(T.emit),
    }
    names = names or list(benches)
    print("name,us_per_call,derived")
    for n in names:
        benches[n]()


if __name__ == "__main__":
    main()
