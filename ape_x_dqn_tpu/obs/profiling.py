"""Continuous perf observability: device-time attribution, compile
telemetry, and a perf-regression engine (ISSUE 8, the third obs plane).

PERF.md's roofline study was a one-off manual exercise; this module
turns it into live gauges riding the existing registry/JSONL surface so
`obs/report.py` renders a roofline section from any run with obs on:

- `StageProfiler` — per-jit wall-time windows around the split
  sample_k/learn_k stages (and the fused train dispatch + ingest
  staging). Every window is `jax.block_until_ready`-bracketed by its
  caller, so its wall time IS dispatch+device time: the single-process
  trainer brackets each stage whenever obs is on, the threaded driver
  only a sampled 1-in-N of its dispatches and ships
  (ObsConfig.profile_windows), after it has released the state lock.
  Combined with `compiled.cost_analysis()` FLOP / bytes-accessed
  estimates captured at warmup, each window publishes per-stage
  `device_ms` gauges and — only on a device whose peaks are known
  (`device_peaks`, or the ObsConfig overrides) — `mfu` and
  `hbm_bw_frac`. NOTE: the compiler FLOP count omits most conv FLOPs
  (~0.9 vs ~47.9 analytic GFLOP/step — PERF.md), so the live MFU gauge
  is a LOWER bound; the analytic count kept with the benchmark
  (benchmarks/harness, `learner.mfu`) is the authority.
- `CompileWatcher` — a process-global jax compile interceptor
  (jax.monitoring's backend_compile duration event) counting compiles,
  compile wall-time, and cumulative executable-cache growth. This
  instruments the known XLA accumulation SIGSEGV that forced
  tests/run_chunked.sh: the crash correlates with per-process compile
  count, which is now a monitored quantity (`compile_cache_entries`
  healthy-range row in obs/report.py).
- `PerfMonitor` — rolling EWMA baselines over grad-steps/s, env-fps
  and ingest rows/s with an attributed `PerfDegradation` obs event
  (warn, never fatal — distinct from StallError: the run keeps going,
  the artifact says it got slower) when a window drops below a
  configurable fraction of its baseline. Evaluated locally, and via
  the PR 6 telemetry frames per-peer on the learner (peer attribution
  rides the event).

Gauges are default-on when obs is enabled; the windows that feed them
on the threaded driver's async paths (train dispatch, ingest ship) are
sampled and default-off (ObsConfig.profile_windows), so the learner's
queued dispatches, the zero-copy pipeline's overlap — and every jit —
stay untouched unless explicitly asked for. Disabled obs routes
through NullObs and
never imports this module's jax hooks at all.
"""

from __future__ import annotations

import json
import logging
import time
from contextlib import contextmanager
from typing import Any, Callable

from ape_x_dqn_tpu.obs.health import make_lock

# -- device peaks ----------------------------------------------------------

# chip peak (bf16 FLOP/s, HBM bytes/s) by device_kind prefix; the MFU
# and hbm_bw_frac denominators (Google Cloud TPU documentation, per-chip
# figures). A device_kind that is not here has NO roof: the mfu_* /
# hbm_bw_frac_* gauges are then not published at all rather than
# divided by a made-up number — ObsConfig.device_peak_flops /
# device_peak_bytes_per_s are the explicit way in for a new chip.
_PEAKS = (
    ("TPU v5p", 459e12, 2.77e12),
    ("TPU v5 lite", 197e12, 0.82e12),
    ("TPU v5e", 197e12, 0.82e12),
    ("TPU v4", 275e12, 1.23e12),
    ("TPU v3", 123e12, 0.90e12),
    ("TPU v2", 46e12, 0.70e12),
)


def device_peaks(device=None) -> tuple[float, float] | None:
    """(peak FLOP/s, peak HBM bytes/s) for `device` (default: device
    0), or None when its device_kind is not in the table."""
    import jax

    if device is None:
        device = jax.devices()[0]
    kind = device.device_kind.lower()
    for prefix, flops, bw in _PEAKS:
        if kind.startswith(prefix.lower()):
            return flops, bw
    return None


def compiled_cost(compiled) -> tuple[float, float]:
    """(flops, bytes accessed) per dispatch from an AOT-compiled jit's
    XLA cost analysis; (0, 0) when the backend reports none."""
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        flops = float(cost.get("flops", 0.0) or 0.0)
        nbytes = float(cost.get("bytes accessed", 0.0) or 0.0)
        return max(flops, 0.0), max(nbytes, 0.0)
    except Exception:  # noqa: BLE001 - strictly best-effort metadata
        return 0.0, 0.0


# -- compile telemetry -----------------------------------------------------


class CompileWatcher:
    """Process-global compile interceptor: one jax.monitoring duration
    listener (there is no unregister in this jax version, so the
    listener is installed once per process and never removed) counting
    backend compiles and their wall time.

    `entries` is the cumulative executable count this process has
    built — the quantity whose unbounded growth in a long-lived CPU
    client precedes the known XLA teardown SIGSEGV (run_chunked.sh's
    raison d'etre). jax.clear_caches() frees the executables but the
    native-side footprint scar remains, so the gauge is deliberately
    monotonic: it tracks compile WORK done, not live cache size."""

    _instance: "CompileWatcher | None" = None

    def __init__(self):
        self._lock = make_lock("profiling.compile_watcher")
        self.compiles = 0  # guarded-by: _lock
        self.compile_s = 0.0  # guarded-by: _lock

    @classmethod
    def install(cls) -> "CompileWatcher":
        if cls._instance is not None:
            return cls._instance
        watcher = cls()
        from jax._src import dispatch, monitoring

        event = dispatch.BACKEND_COMPILE_EVENT

        def _on_duration(name: str, dur: float, **kw: Any) -> None:
            if name != event:
                return
            with watcher._lock:
                watcher.compiles += 1
                watcher.compile_s += float(dur)

        monitoring.register_event_duration_secs_listener(_on_duration)
        cls._instance = watcher
        return watcher

    def snapshot(self) -> tuple[int, float]:
        with self._lock:
            return self.compiles, self.compile_s

    @property
    def entries(self) -> int:
        with self._lock:
            return self.compiles


class CompileTelemetry:
    """Per-Obs view over the process-global watcher: publishes the
    delta since the last publish as counters (so a run's JSONL carries
    only ITS compiles, not a prior run's in the same process) plus the
    cumulative cache-growth gauge."""

    def __init__(self):
        self.watcher = CompileWatcher.install()
        n, s = self.watcher.snapshot()
        self._seen_n = n
        self._seen_s = s

    def publish_into(self, obs) -> None:
        n, s = self.watcher.snapshot()
        dn, ds = n - self._seen_n, s - self._seen_s
        self._seen_n, self._seen_s = n, s
        if dn > 0:
            obs.count("jit_compiles", dn)
            obs.count("jit_compile_ms", ds * 1e3)
        obs.gauge("compile_cache_entries", self.watcher.entries)


def install_compile_log(path: str) -> None:
    """Append one JSON line {argv, jit_compiles, jit_compile_ms} to
    `path` at process exit — the per-file compile-cache growth record
    tests/run_chunked.sh logs (APEX_COMPILE_LOG) to keep the SIGSEGV
    workaround a monitored quantity instead of folklore."""
    import atexit
    import sys

    watcher = CompileWatcher.install()
    base_n, base_s = watcher.snapshot()

    def _flush() -> None:
        n, s = watcher.snapshot()
        try:
            with open(path, "a") as fh:
                fh.write(json.dumps({
                    "argv": sys.argv[1:][:4],
                    "jit_compiles": n - base_n,
                    "jit_compile_ms": round((s - base_s) * 1e3, 1),
                }) + "\n")
        except OSError:
            pass  # a vanished log dir must not break interpreter exit

    atexit.register(_flush)


# -- device-time attribution ----------------------------------------------

# the observed stage vocabulary; every member has literal gauge
# emission sites in _publish_stage below (the obs-names checker
# cross-references string literals only). "train_dist" is the dist
# learner's fused train_many dispatch (parallel/dist_learner.py): the
# same roofline math against the same chip peaks, under its own gauge
# names so a mesh run's per-dp attribution never aliases single-chip
# "train" history (ISSUE 9 multichip lane)
STAGES = ("sample_k", "learn_k", "train", "train_dist", "ingest")


class StageProfiler:
    """Wall-time windows + cost-analysis roofs for the learner's
    device stages. Callers guarantee the window body ends in a
    block_until_ready, so window wall time is honest dispatch+device
    time."""

    def __init__(self, obs, peak_flops: float = 0.0,
                 peak_bw: float = 0.0, ewma_alpha: float = 0.25):
        self._obs = obs
        self._alpha = ewma_alpha
        self._lock = make_lock("profiling.stages")
        # stage -> {"flops_per_step", "bytes_per_step", "ms"(ewma)}
        self._stages: dict[str, dict[str, float]] = {}  # guarded-by: _lock
        self._peak_override = (peak_flops, peak_bw)
        self._peaks_resolved: tuple[float, float] | None = None

    def _peaks(self) -> tuple[float, float]:
        """(peak FLOP/s, peak bytes/s), resolved on first use: explicit
        overrides win, the device_peaks() table fills the rest, and 0.0
        means no roof is known for that axis."""
        if self._peaks_resolved is None:
            flops, bw = self._peak_override
            if not (flops and bw):
                table = device_peaks() or (0.0, 0.0)
                flops, bw = flops or table[0], bw or table[1]
            self._peaks_resolved = (flops, bw)
        return self._peaks_resolved

    def attached(self, stage: str) -> bool:
        with self._lock:
            return stage in self._stages

    def attach(self, stage: str, steps: int = 1,
               compiled: Any = None,
               compile_fn: Callable[[], Any] | None = None) -> None:
        """Record a stage's per-step FLOP/byte roof from an (AOT)
        compiled executable covering `steps` steps. Idempotent; the
        lazy `compile_fn` form is only invoked on first attach (drivers
        pass `lambda: jit.lower(...).compile()`, which populates the
        jit call cache — no second compile when the real call runs)."""
        with self._lock:
            if stage in self._stages:
                return
        if compiled is None and compile_fn is not None:
            try:
                compiled = compile_fn()
            except Exception:  # noqa: BLE001 - attribution is best-effort
                compiled = None
        flops, nbytes = compiled_cost(compiled) if compiled is not None \
            else (0.0, 0.0)
        steps = max(int(steps), 1)
        with self._lock:
            self._stages.setdefault(stage, {
                "flops_per_step": flops / steps,
                "bytes_per_step": nbytes / steps,
                "ms": 0.0,
            })

    @contextmanager
    def window(self, stage: str, steps: int = 1):
        t0 = time.perf_counter()
        yield
        self.record(stage, time.perf_counter() - t0, steps)

    def record(self, stage: str, wall_s: float, steps: int = 1) -> None:
        if wall_s <= 0.0:
            return
        peak_flops, peak_bw = self._peaks()
        with self._lock:
            st = self._stages.get(stage)
            if st is None:
                st = self._stages[stage] = {
                    "flops_per_step": 0.0, "bytes_per_step": 0.0,
                    "ms": 0.0}
            ms = wall_s * 1e3
            st["ms"] = ms if st["ms"] == 0.0 else (
                (1 - self._alpha) * st["ms"] + self._alpha * ms)
            flops = st["flops_per_step"] * steps
            nbytes = st["bytes_per_step"] * steps
            dev_ms = st["ms"]
        # a fraction of an unknown roof is not a number: None skips
        # the gauge (device_ms_* never depends on a roof)
        mfu = (flops / wall_s) / peak_flops if peak_flops else None
        bw = (nbytes / wall_s) / peak_bw if peak_bw else None
        _publish_stage(self._obs, stage, mfu, bw, dev_ms)


def _publish_stage(obs, stage: str, mfu: float | None,
                   bw_frac: float | None, dev_ms: float) -> None:
    """Literal per-stage gauge emissions — spelled out per stage so the
    apexlint obs-names checker (string literals only) cross-references
    every row both ways. None = no roof known, gauge not published."""
    if stage == "sample_k":
        if mfu is not None:
            obs.gauge("mfu_sample_k", mfu)
        if bw_frac is not None:
            obs.gauge("hbm_bw_frac_sample_k", bw_frac)
        obs.gauge("device_ms_sample_k", dev_ms)
    elif stage == "learn_k":
        if mfu is not None:
            obs.gauge("mfu_learn_k", mfu)
        if bw_frac is not None:
            obs.gauge("hbm_bw_frac_learn_k", bw_frac)
        obs.gauge("device_ms_learn_k", dev_ms)
    elif stage == "train":
        if mfu is not None:
            obs.gauge("mfu_train", mfu)
        if bw_frac is not None:
            obs.gauge("hbm_bw_frac_train", bw_frac)
        obs.gauge("device_ms_train", dev_ms)
    elif stage == "train_dist":
        if mfu is not None:
            obs.gauge("mfu_train_dist", mfu)
        if bw_frac is not None:
            obs.gauge("hbm_bw_frac_train_dist", bw_frac)
        obs.gauge("device_ms_train_dist", dev_ms)
    elif stage == "ingest":
        # staging/ship is a pure-bandwidth stage: no MFU roof
        if bw_frac is not None:
            obs.gauge("hbm_bw_frac_ingest", bw_frac)
        obs.gauge("device_ms_ingest", dev_ms)


def publish_multichip(obs, efficiency: float | None = None,
                      fill_min: float | None = None,
                      fill_max: float | None = None) -> None:
    """Literal gauge emissions for the dp-scaling plane (ISSUE 9):

    - dp_scaling_efficiency: grad-steps/s at dp normalized by dp x the
      dp=1 rate — 1.0 is linear scaling. Only a sweep that also ran
      dp=1 has the baseline to publish it; live driver runs carry the
      fill gauges.
    - replay_shard_fill_min / _max: bounds of per-shard replay
      occupancy fractions. Lockstep ingest keeps these equal; a gap
      means shards are filling unevenly and the stratified sampler is
      over-sampling (and down-weighting) the starved shards.

    None skips a gauge — callers publish what they actually measured.
    """
    if efficiency is not None:
        obs.gauge("dp_scaling_efficiency", efficiency)
    if fill_min is not None:
        obs.gauge("replay_shard_fill_min", fill_min)
    if fill_max is not None:
        obs.gauge("replay_shard_fill_max", fill_max)


# -- perf-regression engine ------------------------------------------------


class PerfMonitor:
    """Rolling EWMA baselines over throughput rates; a window below
    `frac` of its baseline emits ONE attributed PerfDegradation obs
    event per cooldown — a warning in the artifact, never an exception
    (deliberately distinct from StallError: slow is survivable,
    silent is not)."""

    def __init__(self, obs, metrics, frac: float = 0.5,
                 alpha: float = 0.1, min_samples: int = 8,
                 cooldown_s: float = 30.0):
        self._obs = obs
        self._metrics = metrics
        self.frac = frac
        self._alpha = alpha
        self._min_samples = min_samples
        self._cooldown_s = cooldown_s
        self._lock = make_lock("profiling.perf_monitor")
        # (peer, name) -> {"ewma", "n", "last_fire"}
        self._series: dict[tuple[str, str], dict] = {}  # guarded-by: _lock
        # fire listeners (the remediation plane subscribes here):
        # called OUTSIDE the monitor lock, once per emitted event, with
        # (name, value, baseline, step, peer). Append-only at wiring
        # time, so iteration is safe without the lock.
        self._listeners: list = []

    def add_listener(self, cb) -> None:
        self._listeners.append(cb)

    def observe(self, name: str, value: float, step: int = 0,
                peer: str = "") -> None:
        value = float(value)
        if value != value or value < 0.0:  # NaN / nonsense rate
            return
        now = time.monotonic()
        fire = False
        baseline = 0.0
        with self._lock:
            s = self._series.setdefault((peer, name), {
                # -inf, not 0.0: monotonic() starts near host boot, so
                # a 0.0 seed would mute the first cooldown_s of uptime
                "ewma": value, "n": 0, "last_fire": float("-inf")})
            baseline = s["ewma"]
            degraded = (s["n"] >= self._min_samples
                        and baseline > 0.0
                        and value < self.frac * baseline)
            if degraded and now - s["last_fire"] >= self._cooldown_s:
                s["last_fire"] = now
                fire = True
            # the baseline keeps absorbing the new regime (slowly):
            # a persistent slowdown fires once per cooldown, then
            # becomes the new normal rather than alerting forever
            s["ewma"] = (1 - self._alpha) * baseline + self._alpha * value
            s["n"] += 1
        if not peer:
            self._publish_local(name, baseline if baseline else value)
        if fire:
            self._obs.count("perf_degradations")
            self._metrics.log(
                step, perf_degradation=name,
                perf_peer=peer or None,
                perf_value=round(value, 3),
                perf_baseline=round(baseline, 3),
                perf_frac=self.frac)
            for cb in self._listeners:
                try:
                    cb(name, value, baseline, step, peer)
                except Exception:  # noqa: BLE001 - warn-only plane
                    logging.getLogger(__name__).warning(
                        "perf-degradation listener failed",
                        exc_info=True)

    def _publish_local(self, name: str, ewma: float) -> None:
        # literal emissions per tracked local rate (obs-names contract)
        if name == "grad_steps_per_s":
            self._obs.gauge("ewma_grad_steps_per_s", ewma)
        elif name == "env_fps":
            self._obs.gauge("ewma_env_fps", ewma)
        elif name == "ingest_rows_per_s":
            self._obs.gauge("ewma_ingest_rows_per_s", ewma)
