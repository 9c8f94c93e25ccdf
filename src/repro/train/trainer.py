"""Production training loop: data prefetch, checkpoint/restart, failure
recovery, straggler mitigation, metrics.

ONE `Trainer` for every parallelism layout: it resolves a frozen
`ParallelPlan` via `core/api.parallelize` and drives the plan's train step —
whole-model SimpleFSDP at pp=1, the staged GPipe/1F1B pipeline (per-stage
SimpleFSDP storage, models' stage-partition contract) when `dcfg.pp_axis`
is set.  pp x dp x tp is a config flip, not a different trainer (the old
`PipelineTrainer` is gone; bring-your-own-stage modules keep
`train_step.make_pipeline_train_step`).

`Trainer.run` survives injected failures by restarting from the newest
checkpoint (same or different mesh — checkpoints are topology-independent:
they always store the PLAIN storage layout, staged layouts are converted on
save/restore, so a run can move between pipeline degrees across restarts);
see ft/failures.py for what is simulated vs. real on this container.
"""

from __future__ import annotations

import dataclasses
import logging
import math

import jax
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.core.api import parallelize
from repro.core.dist import DistConfig
from repro.core.obs.spans import gc_span, span
from repro.data.pipeline import DataConfig, SyntheticC4, adapt_batch
from repro.ft.failures import (FailureSource, StepTimer, StragglerMonitor)
from repro.models.common import ShapeConfig
from repro.optim.adamw import AdamWConfig
from repro.train.train_step import (default_schedule, init_train_state,
                                    train_state_shardings)

log = logging.getLogger("repro.trainer")


@dataclasses.dataclass
class TrainerConfig:
    ckpt_dir: str                     # a run resumes from the newest
                                      # checkpoint here: give each run its own
    total_steps: int = 100
    ckpt_every: int = 50
    log_every: int = 10
    warmup: int = 10
    async_ckpt: bool = False
    max_restarts: int = 3
    stop_after: int | None = None     # pause the job early (schedule horizon
                                      # stays total_steps — used for resume
                                      # tests and preemption drills)
    metrics_jsonl: str | None = None  # append a registry snapshot here at
                                      # every log interval (core/obs)
    # profile-guided replanning (core/obs/profile + calibrate): when the
    # step_time drift |rel| stays above replan_threshold for
    # replan_patience consecutive steps, harvest a MeasuredProfile and
    # re-run the planners under calibration.  replan_apply additionally
    # restarts the loop onto the new plan through the checkpoint path.
    replan_threshold: float | None = None
    replan_patience: int = 3
    replan_apply: bool = False
    replan_profile_steps: int = 2


class Trainer:
    def __init__(self, model, dcfg: DistConfig, shape: ShapeConfig,
                 ocfg: AdamWConfig, tcfg: TrainerConfig,
                 failure_source: FailureSource | None = None,
                 seed: int = 0, registry=None):
        from repro.core.obs import (DriftMonitor, MetricsRegistry,
                                    modeled_step_time)
        from repro.train.train_step import step_wire_metrics

        # observability: one registry + drift monitor per trainer; the
        # plan's own step-time promise and per-step wire bytes are frozen
        # up front so the run loop only records measurements. The registry
        # comes first: it holds the set-up's own spans (core/obs.spans).
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        with self._span("trainer_init"):
            self.model, self.dcfg, self.shape = model, dcfg, shape
            self.ocfg, self.tcfg = ocfg, tcfg
            self.failures = failure_source or FailureSource()
            self.straggler = StragglerMonitor()
            with self._span("checkpointer"):
                self.ckpt = Checkpointer(tcfg.ckpt_dir,
                                         async_save=tcfg.async_ckpt)
            with self._span("data"):
                self.data = SyntheticC4(DataConfig(
                    vocab=model.cfg.vocab, seq_len=shape.seq_len,
                    global_batch=shape.global_batch, seed=seed))
            self._seed = seed
            sched = default_schedule(ocfg, tcfg.total_steps, tcfg.warmup)
            with self._span("parallelize"):
                self.par = parallelize(model, dcfg, shape)
            self.plan = self.par.plan
            self.mesh = self.par.mesh
            with self._span("train_step"):
                self.step_fn = self.par.train_step(ocfg, sched)
            self.history: list[dict] = []
            self.restarts = 0
            # profile-guided replanning state: drift streak, the latest
            # harvested MeasuredProfile, and one delta record per replan
            self._drift_streak = 0
            self._replan_pending = False
            # the first step of each step_fn compiles it: its wall is no
            # steady-state measurement for a replan to calibrate against
            self._fresh_fn = True
            self._compile_steps: set[int] = set()
            self.profile = None
            self.replans: list[dict] = []
            self.drift = DriftMonitor(self.registry)
            with self._span("cost_model"):
                self._modeled_step_s = modeled_step_time(model, self.plan,
                                                         shape)
                self._wire = step_wire_metrics(model, self.plan)
            if self.plan.memory is not None:
                log.info("plan: %s", self.plan.describe())
                for b in self.plan.memory.breakdown:
                    log.info("modeled peak %s", b.describe())

    def _span(self, name: str) -> span:
        return span(name, self.registry)

    def memory_report(self, measured: bool = True) -> dict:
        """Modeled (live-range simulator, core/memory) vs measured (XLA
        ``memory_analysis`` of THIS trainer's compiled step) per-device
        peak.  `measured=False` skips the extra compile and reports the
        model side only."""
        mem_plan = self.plan.memory
        rep = {
            "modeled_peak_bytes": mem_plan.peak if mem_plan else None,
            "policy_spec": mem_plan.policy_spec if mem_plan else
            self.dcfg.remat,
            "per_stage": [b.describe() for b in mem_plan.breakdown]
            if mem_plan else [],
        }
        if measured:
            from repro.optim.adamw import init_opt_state
            params_abs = self.par.abstract_storage
            opt_abs = jax.eval_shape(
                lambda s: init_opt_state(s, self.dcfg), params_abs)
            batch_abs = self.model.input_specs(self.shape, self.dcfg)
            m = self.step_fn.lower(params_abs, opt_abs,
                                   batch_abs).compile().memory_analysis()
            meas = (m.argument_size_in_bytes + m.temp_size_in_bytes
                    + m.output_size_in_bytes - m.alias_size_in_bytes)
            rep["measured_peak_bytes"] = meas
            if mem_plan is not None:
                rep["modeled_over_measured"] = mem_plan.peak / max(1, meas)
                # ONE audited modeled-vs-measured path (core/obs):
                # record_peak writes the gauges and formats the line the
                # dryrun's [mem] print shares
                log.info("memory: %s", self.registry.record_peak(
                    "train", mem_plan.peak, meas,
                    note=f"remat={rep['policy_spec']}"))
                self.drift.record("peak_memory", mem_plan.peak, meas)
        return rep

    # ------------------------------------------------------------------ --
    def _init_or_restore(self, key):
        """Train state on the shardings the step lays it out in (state
        placed otherwise traces, and compiles, a second step program)."""
        shardings = train_state_shardings(self.par)
        latest = self.ckpt.latest_step()
        if latest is None:
            # made where it lives: each device builds only its own shards,
            # and no device ever holds the whole model
            storage, opt_state = jax.jit(
                lambda k: init_train_state(self.model, self.dcfg, k,
                                           plan=self.plan),
                out_shardings=shardings)(key)
            return storage, opt_state, 0
        storage, opt_state, _ = self.ckpt.restore(latest, self.model,
                                                  self.dcfg)
        # checkpoints hold the plain layout; stage it for this plan
        storage = self.par.stage_storage(storage)
        if self.plan.pipelined:
            from repro.models import staging
            opt_state = staging.stage_opt_state(
                opt_state, self.plan.stage, self.dcfg,
                self.par.pipe_sharded)
        log.info("restored step %d", latest)
        storage, opt_state = jax.device_put((storage, opt_state), shardings)
        return storage, opt_state, latest

    def _save(self, step, storage, opt_state):
        if self.plan.pipelined:
            from repro.models import staging
            storage = self.par.unstage_storage(storage)
            opt_state = staging.unstage_opt_state(
                opt_state, self.plan.stage, self.dcfg,
                self.par.pipe_sharded)
        self.ckpt.save(step, storage, opt_state, self.model, self.dcfg)

    def _batch(self, step):
        batch = adapt_batch(
            self.data.batch(step),
            self.model.input_specs(self.shape, self.dcfg),
            step=step, seed=self._seed)
        if self.dcfg.cp_size > 1:
            # zigzag sequence permutation so the contiguous ctx sharding
            # delivers each rank its load-balanced chunks (core/context.py)
            from repro.core.context import zigzag_batch
            batch = zigzag_batch(batch, self.dcfg)
        return batch

    def _record_step(self, step: int, dt: float, metrics) -> None:
        """Mirror one completed step into the registry + drift monitor."""
        r = self.registry
        r.counter("train/steps").inc()
        r.gauge("train/step_time_s").set(dt)
        r.gauge("train/tokens_per_s").set(
            self.shape.seq_len * self.shape.global_batch / max(1e-9, dt))
        r.gauge("train/grad_norm").set(float(metrics["grad_norm"]))
        r.gauge("train/loss").set(float(metrics["loss"]))
        for prec, nbytes in self._wire["by_precision"].items():
            r.counter(f"train/wire_bytes/{prec}").inc(nbytes)
        if self._modeled_step_s is not None:
            rel = self.drift.record("step_time", self._modeled_step_s, dt,
                                    step=step)
            if self.tcfg.replan_threshold is not None \
                    and math.isfinite(rel):
                if abs(rel) > self.tcfg.replan_threshold:
                    self._drift_streak += 1
                    if self._drift_streak >= self.tcfg.replan_patience:
                        self._replan_pending = True
                else:
                    self._drift_streak = 0

    def _replan(self, step, storage, opt_state):
        """Profile-guided replanning: harvest a `MeasuredProfile` against
        the drifting plan, re-run the planners under calibration, log the
        delta, and — when `replan_apply` — restart the loop onto the new
        plan through the checkpoint path (the same topology-independent
        restart the failure path uses).  Returns the (possibly restaged)
        train state."""
        from repro.core.obs import calibrated_step_time, profile_step
        from repro.core.obs import replan as obs_replan

        self._replan_pending = False
        self._drift_streak = 0
        rows = [r for r in self.drift.records.get("step_time", [])
                if r["step"] not in self._compile_steps]
        recent = [r["measured"]
                  for r in rows[-max(1, self.tcfg.replan_patience):]]
        wall = sum(recent) / len(recent) if recent else None
        try:
            self.profile = profile_step(
                self.model, self.plan, self.shape,
                steps=self.tcfg.replan_profile_steps, wall_step_s=wall)
            new_plan, delta = obs_replan(self.model, self.plan, self.shape,
                                         self.profile)
        except Exception:
            log.exception("replan failed at step %d; keeping current plan",
                          step)
            return storage, opt_state
        delta["step"] = step
        delta["applied"] = False
        self.replans.append(delta)
        r = self.registry
        r.counter("replan/count").inc()
        for k in ("modeled_step_before_s", "modeled_step_after_s"):
            if delta[k] is not None:
                r.gauge(f"replan/{k}").set(delta[k])
        log.info("replan at step %d: changed=%s gain=%s fields=%s", step,
                 delta["changed"], delta["modeled_gain_s"],
                 sorted(delta["fields"]))
        if not (self.tcfg.replan_apply and delta["changed"]):
            return storage, opt_state
        # restart onto the new plan: checkpoints store the plain layout,
        # so save, rebuild the parallelized bundle, and restore staged
        self._save(step, storage, opt_state)
        self.ckpt.wait()
        sched = default_schedule(self.ocfg, self.tcfg.total_steps,
                                 self.tcfg.warmup)
        self.par = parallelize(self.model, self.dcfg, self.shape,
                               plan=new_plan)
        self.plan = self.par.plan
        self.mesh = self.par.mesh
        self.step_fn = self.par.train_step(self.ocfg, sched)
        self._fresh_fn = True
        try:
            self._modeled_step_s = calibrated_step_time(
                self.model, self.plan, self.shape, self.profile)
        except Exception:
            self._modeled_step_s = None
        storage, opt_state, _ = self._init_or_restore(
            jax.random.PRNGKey(self._seed))
        delta["applied"] = True
        log.info("replan applied at step %d: %s", step, self.plan.describe())
        return storage, opt_state

    def run(self, key=None):
        """The training loop. Its parts are host spans (core/obs.spans):
        restore, batch, step (the dispatch), sync, record, replan and
        checkpoint, with every Python garbage collection a `gc` span."""
        with gc_span(self.registry):
            return self._run(key)

    def _run(self, key):
        key = key if key is not None else jax.random.PRNGKey(0)
        with self._span("restore"):
            storage, opt_state, start = self._init_or_restore(key)
        step = start
        stop_at = self.tcfg.stop_after or self.tcfg.total_steps
        while step < stop_at:
            if self.failures.check(step):
                self.restarts += 1
                if self.restarts > self.tcfg.max_restarts:
                    raise RuntimeError("restart budget exhausted")
                log.warning("failure detected at step %d; restarting", step)
                with self._span("restore"):
                    self.ckpt.wait()
                    storage, opt_state, step = self._init_or_restore(key)
                continue

            with self._span("batch"):
                batch = self._batch(step)
            with StepTimer() as t:
                with self._span("step"):
                    out = self.step_fn(storage, opt_state, batch)
                with self._span("sync"):
                    storage, opt_state, metrics = jax.block_until_ready(out)
                    metrics = jax.tree.map(np.asarray, metrics)
            verdict = self.straggler.observe(t.dt)
            if verdict == "escalate":
                log.warning("straggler escalation at step %d", step)
            step += 1
            if self._fresh_fn:
                self._compile_steps.add(step)
                self._fresh_fn = False
            with self._span("record"):
                self._record_step(step, t.dt, metrics)
            if self._replan_pending:
                with self._span("replan"):
                    storage, opt_state = self._replan(step, storage,
                                                      opt_state)
            if step % self.tcfg.log_every == 0 or step in (1, stop_at):
                with self._span("record"):
                    self._log_step(step, t.dt, metrics)
            if step % self.tcfg.ckpt_every == 0 \
                    or step in (self.tcfg.total_steps, stop_at):
                with self._span("checkpoint"):
                    self._save(step, storage, opt_state)
        with self._span("checkpoint"):
            self.ckpt.wait()
        return storage, opt_state, self.history

    def _log_step(self, step: int, dt: float, metrics) -> None:
        self.history.append(
            {"step": step, "dt": dt,
             **{k: float(v) for k, v in metrics.items()}})
        log.info("step %d loss %.4f gnorm %.3f %.0fms", step,
                 metrics["loss"], metrics["grad_norm"], dt * 1e3)
        if self.tcfg.metrics_jsonl:
            self.registry.dump_jsonl(self.tcfg.metrics_jsonl, step=step)
