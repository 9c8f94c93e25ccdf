"""Unified telemetry: plan-aligned trace timelines (`trace`), the typed
per-step metrics registry (`metrics`), the modeled-vs-measured drift
monitor (`drift`), the profile -> calibrate -> replan loop
(`profile` + `calibrate`), and the named parts of a step: device scopes and
host spans (`spans`).

The observability counterpart of the plan-centric architecture: every
cost model in the repo (collective exposure, pipeline bubble, memory
simulator, ring hops, serving roofline) renders into ONE Chrome-trace
timeline and ONE registry; the drift monitor scores the residuals per
subsystem, the step profiler measures the executed schedule, and
calibration feeds the measured rates back into the planners so a drifted
plan can be re-planned against reality.
"""

from repro.core.obs.calibrate import (calibrated_block_stats,
                                      calibrated_step_time, calibration,
                                      replan)
from repro.core.obs.drift import SUBSYSTEMS, DriftMonitor, modeled_step_time
from repro.core.obs.metrics import (Counter, Gauge, Histogram,
                                    MetricsRegistry, default_registry)
from repro.core.obs.profile import MeasuredProfile, profile_step
from repro.core.obs.spans import SCOPES, gc_span, span, span_times
from repro.core.obs.trace import (PID_MEASURED, PID_MODELED, PID_SERVING,
                                  TID_COMM, TID_COMPUTE, TID_STRAGGLER,
                                  TraceBuilder, comm_windows,
                                  emit_comm_lanes, lane_spans,
                                  measured_overlay, nonoverlapped_comm_s,
                                  pipeline_lanes, plan_comm_windows,
                                  plan_trace, ring_lanes, serving_lanes)

__all__ = [
    "SUBSYSTEMS", "DriftMonitor", "modeled_step_time",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "default_registry",
    "MeasuredProfile", "profile_step",
    "SCOPES", "gc_span", "span", "span_times",
    "calibrated_block_stats", "calibrated_step_time", "calibration",
    "replan",
    "PID_MEASURED", "PID_MODELED", "PID_SERVING", "TID_COMM", "TID_COMPUTE",
    "TID_STRAGGLER", "TraceBuilder", "comm_windows", "emit_comm_lanes",
    "lane_spans", "measured_overlay", "nonoverlapped_comm_s",
    "pipeline_lanes", "plan_comm_windows", "plan_trace", "ring_lanes",
    "serving_lanes",
]
