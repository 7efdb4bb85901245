"""Obs facade: one object the runtime threads spans/metrics/beats into.

Drivers build one `Obs` from `configs.ObsConfig` and hand it to their
components (actors, ingest, learner loop, inference server). Every
call site goes through this facade so the disabled path is a method
call on the `NullObs` singleton — no conditionals in runtime code, and
~zero overhead when observability is off (the acceptance bar: the
learner's rate unchanged with ObsConfig disabled, which trivially holds
because the learner jits are untouched and disabled drivers never call
into numpy or locks here).

First-class Ape-X health instruments (ISSUE 2 / Horgan et al. 2018 §4):
- hist `sample_age_steps`: learner grad-step minus the grad-step at
  which each sampled transition was written (via `SampleAgeTracker`,
  a host-side mirror of the flat ring's skip-to-head write cursor).
- hist `param_lag_steps`: learner grad-step minus the param version
  the inference server served a batch with (actor parameter lag).
- hist `td_abs`: per-dispatch mean |TD| (the priority signal).
- hist `server_batch_items`: dynamic-batching fill.
- gauges `replay_occupancy`, `server_queue_depth`, counters for adds,
  dispatches, stall strikes.
"""

from __future__ import annotations

import os
import tempfile
from typing import Any

import numpy as np

from ape_x_dqn_tpu.obs.blackbox import NULL_BLACKBOX, FlightRecorder
from ape_x_dqn_tpu.obs.health import (
    HeartbeatRegistry, HeartbeatWatchdog, StallError)
from ape_x_dqn_tpu.obs.registry import MetricRegistry, geometric_edges
from ape_x_dqn_tpu.obs.trace import NULL_SPAN, NULL_TRACER, SpanTracer

AGE_EDGES = geometric_edges(1.0, 1e6, per_decade=4)
LAG_EDGES = geometric_edges(1.0, 1e5, per_decade=4)
TD_EDGES = geometric_edges(1e-3, 1e3, per_decade=4)
BATCH_EDGES = tuple(float(2 ** i) for i in range(12))
# inference request latency (enqueue -> result scatter), milliseconds:
# sub-ms when the server keeps up, deadline_ms-ish when batching, and
# unbounded when the queue backs up — the serving-SLO instrument
LATENCY_EDGES = geometric_edges(0.1, 1e4, per_decade=4)
# per-dispatch training loss (learning-health plane, obs/learning.py):
# wide geometric range because a loss spike IS the signal
LOSS_EDGES = geometric_edges(1e-6, 1e3, per_decade=2)


class NullObs:
    """No-op twin: the runtime threads call this when obs is disabled.
    Keep method-for-method parity with Obs."""

    enabled = False
    tracer = NULL_TRACER
    watchdog = None
    profiler = None
    perf = None
    learn = None
    blackbox = NULL_BLACKBOX

    def span(self, name: str, **args: Any):
        return NULL_SPAN

    def record(self, name: str, t0: float, t1: float,
               **args: Any) -> None:
        pass

    def lap(self, name: str, since: tuple | None = None,
            **args: Any) -> None:
        return None

    def stage_window(self, stage: str, steps: int = 1):
        return NULL_SPAN

    def stage_attach(self, stage: str, steps: int = 1,
                     compiled: Any = None, compile_fn=None) -> None:
        pass

    def stage_attached(self, stage: str) -> bool:
        # True: disabled obs never wants the (compiling) attach path
        return True

    def perf_rate(self, name: str, value, step: int = 0,
                  peer: str = "") -> None:
        pass

    def learn_health(self, diag, loss, step: int = 0,
                     tenant: str = "") -> None:
        pass

    def mark(self, name: str, **args: Any) -> None:
        pass

    def register(self, name: str) -> None:
        pass

    def beat(self, name: str, note: str = "") -> None:
        pass

    def clear(self, name: str) -> None:
        pass

    def check_stalled(self) -> None:
        pass

    def observe(self, hist: str, value) -> None:
        pass

    def observe_many(self, hist: str, values) -> None:
        pass

    def gauge(self, name: str, value) -> None:
        pass

    def count(self, name: str, n: float = 1.0) -> None:
        pass

    def set_learner_step(self, step: int) -> None:
        pass

    def on_server_batch(self, items: int, params_version: int,
                        queue_depth: int) -> None:
        pass

    def age_tracker(self, capacity: int) -> "SampleAgeTracker | None":
        return None

    def observe_sample_ages(self, ages) -> None:
        pass

    def log_compiled(self, tag: str, compiled) -> None:
        pass

    def maybe_profile(self, step: int) -> None:
        pass

    def publish(self, step: int) -> None:
        pass

    def close(self, step: int = 0) -> None:
        pass


NULL_OBS = NullObs()


class SampleAgeTracker:
    """Host-side mirror of the flat replay ring's write cursor.

    The device ReplayState records no write times; adding them to the
    storage pytree would grow every add/sample graph for a metric. But
    flat ring writes are sequential with skip-to-head wrap
    (replay/packing.ring_write_start), so the host can mirror the
    cursor exactly: `on_add` stamps the written slots with the current
    grad-step, and `ages(idx, step)` maps sampled slot indices back to
    write steps. Valid for the flat layouts (PrioritizedReplay /
    UniformReplayDevice) whose adds all flow through one host loop —
    the single-process driver's case."""

    def __init__(self, capacity: int):
        self._write_step = np.zeros(capacity, np.int64)
        self._pos = 0
        self._cap = capacity

    def on_add(self, n: int, grad_step: int) -> None:
        if n <= 0:
            return
        n = min(n, self._cap)
        # skip-to-head: a block that would cross the ring boundary
        # restarts at slot 0 (must match replay/packing.ring_write_start)
        start = self._pos if self._pos + n <= self._cap else 0
        self._write_step[start:start + n] = grad_step
        self._pos = (start + n) % self._cap

    def ages(self, idx, grad_step: int) -> np.ndarray:
        slots = np.asarray(idx).ravel()
        return grad_step - self._write_step[slots]


class Obs:
    """Live observability session for one driver run."""

    enabled = True

    def __init__(self, cfg, metrics):
        """cfg: configs.ObsConfig (enabled already checked by build_obs);
        metrics: the run's utils.metrics.Metrics sink."""
        self.cfg = cfg
        self.metrics = metrics
        self.tracer = (SpanTracer(cfg.trace_path, cfg.trace_max_events)
                       if cfg.trace_path else NULL_TRACER)
        self.registry = MetricRegistry()
        self.heartbeats = HeartbeatRegistry()
        self.watchdog = (HeartbeatWatchdog(self.heartbeats,
                                           cfg.heartbeat_timeout_s)
                         if cfg.heartbeat_timeout_s > 0 else None)
        # seed the first-class instruments so a short run publishes
        # empty histograms rather than omitting the keys entirely
        self.registry.histogram("sample_age_steps", AGE_EDGES)
        self.registry.histogram("param_lag_steps", LAG_EDGES)
        self.registry.histogram("td_abs", TD_EDGES)
        self.registry.histogram("server_batch_items", BATCH_EDGES)
        self.registry.histogram("infer_latency_ms", LATENCY_EDGES)
        self.registry.histogram("learn_loss", LOSS_EDGES)
        self._learner_step = 0
        # jax.profiler window: False = armed, True = tracing,
        # None = done/disabled (single capture per run)
        self._prof_state: bool | None = (
            False if getattr(cfg, "jax_profile_dir", "") else None)
        self._prof_from = 0
        self._closed = False
        # continuous perf plane (obs/profiling.py, ISSUE 8): roofline
        # gauges + compile telemetry default-on with obs, the EWMA
        # regression engine likewise; each is individually knob-gated.
        # getattr defaults keep configs predating the knobs working.
        from ape_x_dqn_tpu.obs import profiling

        self.profiler = (profiling.StageProfiler(
            self,
            peak_flops=getattr(cfg, "device_peak_flops", 0.0),
            peak_bw=getattr(cfg, "device_peak_bytes_per_s", 0.0))
            if getattr(cfg, "profile_gauges", True) else None)
        self._compile_telemetry = (
            profiling.CompileTelemetry()
            if getattr(cfg, "compile_telemetry", True) else None)
        self.perf = (profiling.PerfMonitor(
            self, metrics,
            frac=getattr(cfg, "perf_frac", 0.5),
            alpha=getattr(cfg, "perf_ewma_alpha", 0.1),
            min_samples=getattr(cfg, "perf_min_samples", 8),
            cooldown_s=getattr(cfg, "perf_cooldown_s", 30.0))
            if getattr(cfg, "perf_regression", True) else None)
        # learning-health plane (obs/learning.py, ISSUE 10): warn-only
        # anomaly engine over the in-graph learner diagnostics
        from ape_x_dqn_tpu.obs import learning

        self.learn = (learning.LearnMonitor(
            self, metrics,
            spike_mult=getattr(cfg, "learn_spike_mult", 10.0),
            alpha=getattr(cfg, "learn_ewma_alpha", 0.2),
            min_samples=getattr(cfg, "learn_min_samples", 8),
            cooldown_s=getattr(cfg, "learn_cooldown_s", 30.0))
            if getattr(cfg, "learn_health", True) else None)
        # forensics plane (obs/blackbox.py, ISSUE 17): per-process
        # flight recorder, dumped on crash/stall/SIGUSR2/supervisor
        # request. Default dump dir rides next to the run JSONL;
        # in-memory-metrics runs (tests, embedded probes) fall back to
        # the system temp dir, never the CWD
        if getattr(cfg, "blackbox", True):
            bb_dir = (getattr(cfg, "blackbox_dir", "")
                      or os.path.dirname(
                          getattr(getattr(metrics, "_fh", None),
                                  "name", "") or "")
                      or tempfile.gettempdir())
            self.blackbox = FlightRecorder(
                self, out_dir=bb_dir,
                capacity=getattr(cfg, "blackbox_capacity", 512),
                log_lines=getattr(cfg, "blackbox_log_lines", 64))
            # attributed degradation events flow into the ring so the
            # box tells the story leading up to the dump
            if self.perf is not None:
                self.perf.add_listener(self._blackbox_perf_event)
            if self.learn is not None:
                self.learn.add_listener(self._blackbox_learn_event)
        else:
            self.blackbox = NULL_BLACKBOX

    def _blackbox_perf_event(self, name, value, baseline, step,
                             peer) -> None:
        self.blackbox.record("perf_degradation", component=name,
                             peer=peer, value=round(float(value), 4),
                             baseline=round(float(baseline), 4),
                             step=int(step))

    def _blackbox_learn_event(self, rule, value, baseline, step,
                              tenant) -> None:
        self.blackbox.record("learning_degradation", component=rule,
                             tenant=tenant, value=round(float(value), 4),
                             baseline=round(float(baseline), 4),
                             step=int(step))

    # -- tracing -----------------------------------------------------------

    def span(self, name: str, **args: Any):
        return self.tracer.span(name, **args)

    def record(self, name: str, t0: float, t1: float,
               **args: Any) -> None:
        self.tracer.record(name, t0, t1, **args)

    def lap(self, name: str, since: tuple | None = None,
            **args: Any) -> tuple:
        return self.tracer.lap(name, since, **args)

    def mark(self, name: str, **args: Any) -> None:
        self.tracer.mark(name, **args)

    # -- heartbeats / watchdog ---------------------------------------------

    def register(self, name: str) -> None:
        self.heartbeats.register(name)

    def beat(self, name: str, note: str = "") -> None:
        self.heartbeats.beat(name, note)

    def clear(self, name: str) -> None:
        self.heartbeats.clear(name)

    def check_stalled(self) -> None:
        """Called from the driver's (alive) supervisory loop; raises
        StallError attributing the stalest silent component."""
        if self.watchdog is not None:
            try:
                self.watchdog.check()
            except StallError as e:
                # the stall rides the JSONL stream too, so offline
                # report sees it even when the raise is swallowed
                self.count("stall_errors")
                self.metrics.log(self._learner_step,
                                 stall_component=e.component,
                                 stall_staleness_s=e.staleness_s,
                                 stall_note=e.last_note)
                # archive the box BEFORE closing: the StallError is a
                # terminal event and the ring is its evidence
                self.blackbox.record("stall", component=e.component,
                                     staleness_s=round(e.staleness_s, 1),
                                     note=e.last_note)
                self.blackbox.dump("stall", component=e.component,
                                   step=self._learner_step)
                # flush the trace + final snapshot NOW: the artifacts
                # matter most on the crash path, and not every caller
                # wraps its loop in try/finally
                self.close(self._learner_step)
                raise

    # -- instruments -------------------------------------------------------

    def observe(self, hist: str, value) -> None:
        self.registry.histogram(hist).observe(float(value))

    def observe_many(self, hist: str, values) -> None:
        self.registry.histogram(hist).observe_many(values)

    def gauge(self, name: str, value) -> None:
        self.registry.gauge(name).set(float(value))

    def count(self, name: str, n: float = 1.0) -> None:
        self.registry.counter(name).inc(n)

    # -- staleness hooks ---------------------------------------------------

    def set_learner_step(self, step: int) -> None:
        # plain int attr write: GIL-atomic, read by the server thread
        self._learner_step = int(step)

    def on_server_batch(self, items: int, params_version: int,
                        queue_depth: int) -> None:
        """Inference-server hook, once per served batch: parameter lag
        is how many grad-steps the served params trail the learner."""
        self.observe("server_batch_items", items)
        self.observe("param_lag_steps",
                     max(self._learner_step - int(params_version), 0))
        self.gauge("server_queue_depth", queue_depth)
        self.beat("inference-server", f"batch of {items}")

    def age_tracker(self, capacity: int) -> SampleAgeTracker:
        return SampleAgeTracker(capacity)

    def observe_sample_ages(self, ages) -> None:
        self.observe_many("sample_age_steps", ages)

    # -- continuous perf plane (obs/profiling.py) --------------------------

    def stage_window(self, stage: str, steps: int = 1):
        """Device-time attribution window around a block_until_ready-
        bracketed stage dispatch; publishes the stage's mfu /
        hbm_bw_frac / device_ms gauges on exit. No-op context when the
        roofline gauges are knob-disabled."""
        if self.profiler is None:
            return NULL_SPAN
        return self.profiler.window(stage, steps)

    def stage_attach(self, stage: str, steps: int = 1,
                     compiled: Any = None, compile_fn=None) -> None:
        if self.profiler is not None:
            self.profiler.attach(stage, steps, compiled=compiled,
                                 compile_fn=compile_fn)

    def stage_attached(self, stage: str) -> bool:
        return self.profiler is None or self.profiler.attached(stage)

    def perf_rate(self, name: str, value, step: int = 0,
                  peer: str = "") -> None:
        """Feed one throughput-rate sample to the EWMA regression
        engine (warn-only PerfDegradation events)."""
        if self.perf is not None:
            self.perf.observe(name, value, step=step, peer=peer)

    # -- learning-health plane (obs/learning.py) ---------------------------

    def learn_health(self, diag, loss, step: int = 0,
                     tenant: str = "") -> None:
        """Publish one host-read learner diagnostic snapshot (the
        metrics['diag'] pytree) as `learn_*` gauges + the loss hist,
        and feed the warn-only LearnMonitor. Callers must pass values
        already synced by their existing block_until_ready — this
        method only converts ready device scalars (no new syncs)."""
        from ape_x_dqn_tpu.obs import learning

        vals = {k: float(v) for k, v in dict(diag).items()}
        learning.publish_learn(self, vals, tenant=tenant)
        loss = float(loss)
        self.observe("learn_loss", loss)
        if self.learn is not None:
            self.learn.observe(vals, loss, step=step, tenant=tenant)

    # -- jax integration ---------------------------------------------------

    def log_compiled(self, tag: str, compiled) -> None:
        """Record a compiled jit's XLA memory_analysis into the JSONL
        (reuses utils/hbm.py's budget vocabulary: these are the
        measured anchors the static budget is calibrated against)."""
        if not getattr(self.cfg, "hbm_dump", True):
            return
        from ape_x_dqn_tpu.utils.hbm import compiled_memory_summary

        summary = compiled_memory_summary(compiled)
        if summary:
            self.metrics.log(self._learner_step,
                             **{f"hbm/{tag}/{k}": v
                                for k, v in summary.items()})

    def maybe_profile(self, step: int) -> None:
        """Opt-in jax.profiler window (ObsConfig.jax_profile_dir):
        trace `jax_profile_steps` grad-steps starting at the first
        call — the XLA-level twin of the host-side span trace."""
        if self._prof_state is None:
            return
        import jax

        if self._prof_state is False:
            jax.profiler.start_trace(self.cfg.jax_profile_dir)
            self._prof_from = step
            self._prof_state = True
        elif step - self._prof_from >= self.cfg.jax_profile_steps:
            jax.profiler.stop_trace()
            self._prof_state = None
            self.metrics.log(step, profile_trace=self.cfg.jax_profile_dir)

    # -- publication -------------------------------------------------------

    def publish(self, step: int) -> None:
        """Snapshot every instrument + the span aggregates into one
        JSONL record (`span/<name>` dicts carry the stage-time
        breakdown obs/report.py prints)."""
        self.set_learner_step(step)
        # cheap periodic anchor: every dump's ring shows when the last
        # healthy publish happened, whatever else it recorded
        self.blackbox.record("publish", step=int(step))
        if self._compile_telemetry is not None:
            self._compile_telemetry.publish_into(self)
        agg = self.tracer.aggregates()
        extra = {f"span/{name}": stats for name, stats in agg.items()}
        self.registry.publish(self.metrics, step, extra=extra)

    def close(self, step: int = 0) -> None:
        if self._closed:
            return
        self._closed = True
        if self._prof_state is True:  # run ended inside the window
            import jax

            jax.profiler.stop_trace()
            self._prof_state = None
        self.publish(step)
        self.tracer.close()
        # crash hooks must not outlive the session that owns the ring
        self.blackbox.uninstall()


def build_obs(obs_cfg, metrics) -> Obs | NullObs:
    """NULL_OBS unless the config exists and is enabled — drivers call
    this with `getattr(cfg, "obs", None)` so configs predating ObsConfig
    keep working."""
    if obs_cfg is None or not getattr(obs_cfg, "enabled", False):
        return NULL_OBS
    return Obs(obs_cfg, metrics)
