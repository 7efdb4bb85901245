"""Continuous perf plane (obs/profiling.py, ISSUE 8).

Four behaviors from the issue's test checklist: live MFU/roofline
gauges are sane on the catch smoke, the compile watcher counts fresh
jit compiles (delta-published per run), PerfDegradation fires on a
synthetically throttled rate with the right stage name (and per-peer
attribution), and disabled obs emits nothing while never taking any
compiling code path.
"""

import json

from ape_x_dqn_tpu.configs import (EnvConfig, LearnerConfig,
                                   NetworkConfig, ObsConfig,
                                   ReplayConfig, get_config)
from ape_x_dqn_tpu.obs.core import NULL_OBS, build_obs
from ape_x_dqn_tpu.utils.metrics import Metrics


def _smoke_cfg(enabled: bool = True, **obs_kw):
    """Catch smoke at test_obs.py's shapes: sample_chunk=2 routes the
    observed run through the split sample_k/learn_k macro-dispatch."""
    return get_config("pong").replace(
        env=EnvConfig(id="catch", kind="synthetic_atari"),
        network=NetworkConfig(kind="nature_cnn", dueling=True,
                              compute_dtype="float32"),
        replay=ReplayConfig(kind="prioritized", capacity=2048,
                            min_fill=300),
        learner=LearnerConfig(batch_size=16, n_step=3,
                              target_sync_every=16, sample_chunk=2),
        obs=ObsConfig(enabled=enabled, publish_every_steps=50,
                      heartbeat_timeout_s=120.0, **obs_kw),
    )


class _RecorderObs:
    def __init__(self):
        self.counts: dict = {}
        self.gauges: dict = {}

    def count(self, name, n=1.0):
        self.counts[name] = self.counts.get(name, 0.0) + n

    def gauge(self, name, value):
        self.gauges[name] = value


# -- device-time attribution / roofline gauges ------------------------------

def test_mfu_gauges_on_catch_smoke(tmp_path):
    """The live roofline: a real observed catch run publishes per-stage
    mfu/hbm_bw_frac/device_ms gauges with sane values (0 < mfu < 1
    needs cost_analysis FLOPs AND a peak — the CPU test device has no
    table row, so the roof comes in through the explicit ObsConfig
    overrides), and the offline report renders the roofline section
    from the same JSONL."""
    from ape_x_dqn_tpu.obs import report
    from ape_x_dqn_tpu.runtime.single_process import train_single_process

    jsonl = str(tmp_path / "run.jsonl")
    metrics = Metrics(log_path=jsonl)
    cfg = _smoke_cfg(device_peak_flops=512e9,
                     device_peak_bytes_per_s=40e9)
    out = train_single_process(cfg, total_env_frames=420,
                               metrics=metrics, train_every=2)
    metrics.close()
    assert out["grad_steps"] > 0
    recs = [json.loads(line) for line in open(jsonl)]
    snaps = [r for r in recs if "gauge/mfu_sample_k" in r]
    assert snaps, "no roofline gauges reached the JSONL"
    last = snaps[-1]
    for key in ("gauge/mfu_sample_k", "gauge/mfu_learn_k"):
        assert 0.0 < last[key] < 1.0, (key, last[key])
    for key in ("gauge/device_ms_sample_k", "gauge/device_ms_learn_k",
                "gauge/hbm_bw_frac_sample_k",
                "gauge/hbm_bw_frac_learn_k"):
        assert last[key] > 0.0, (key, last[key])
    # compile telemetry rode the same publish stream: this run compiled
    # fresh jits, so at least one snapshot carries a nonzero counter
    assert any(r.get("ctr/jit_compiles", 0) > 0 for r in recs)
    assert last["gauge/compile_cache_entries"] > 0
    # the offline report renders a roofline section with both stages
    text = report.format_report(report.summarize(recs))
    assert "roofline" in text
    assert "sample_k" in text and "learn_k" in text
    assert "compile telemetry:" in text


def test_no_roof_for_unknown_device():
    """A device_kind outside the peaks table (the CPU test device) has
    no roof: device_peaks() says so, and without an explicit override
    StageProfiler publishes device_ms_* but no mfu_* / hbm_bw_frac_*
    gauge — a CPU run never writes under a device metric's name."""
    from ape_x_dqn_tpu.obs.profiling import StageProfiler, device_peaks

    assert device_peaks() is None
    sink = _RecorderObs()
    prof = StageProfiler(sink)
    prof.record("train", 0.01, steps=4)
    prof.record("ingest", 0.01)
    assert set(sink.gauges) == {"device_ms_train", "device_ms_ingest"}
    sink = _RecorderObs()
    prof = StageProfiler(sink, peak_flops=1e12)  # one axis overridden
    prof.record("train", 0.01, steps=4)
    assert set(sink.gauges) == {"device_ms_train", "mfu_train"}


def test_stage_profiler_cost_analysis_present():
    """attach() captures nonzero FLOP/byte roofs from a real compiled
    executable on this backend (the gauge denominators)."""
    import jax
    import jax.numpy as jnp

    from ape_x_dqn_tpu.obs.profiling import compiled_cost

    def f(x):
        return (x @ x.T).sum()

    compiled = jax.jit(f).lower(
        jnp.ones((64, 64), jnp.float32)).compile()
    flops, nbytes = compiled_cost(compiled)
    assert flops > 0.0
    assert nbytes > 0.0


# -- compile telemetry ------------------------------------------------------

def test_compile_watcher_counts_fresh_jit():
    import jax
    import jax.numpy as jnp

    from ape_x_dqn_tpu.obs.profiling import CompileWatcher

    watcher = CompileWatcher.install()
    assert CompileWatcher.install() is watcher  # process singleton
    n0, s0 = watcher.snapshot()

    @jax.jit
    def f(x):
        return x * 2.0 + 1.0

    f(jnp.arange(7, dtype=jnp.float32)).block_until_ready()
    n1, s1 = watcher.snapshot()
    assert n1 > n0
    assert s1 > s0
    assert watcher.entries == n1  # monotonic compile-work ledger


def test_compile_telemetry_publishes_delta_only():
    """A run's JSONL carries only ITS compiles: the per-Obs view
    publishes deltas since construction/last publish, while the cache
    gauge stays the process-cumulative count."""
    import jax
    import jax.numpy as jnp

    from ape_x_dqn_tpu.obs.profiling import CompileTelemetry

    ct = CompileTelemetry()

    @jax.jit
    def g(x):
        return x - 3.0

    g(jnp.arange(5, dtype=jnp.float32)).block_until_ready()
    rec = _RecorderObs()
    ct.publish_into(rec)
    assert rec.counts.get("jit_compiles", 0) >= 1
    assert rec.counts.get("jit_compile_ms", 0) > 0
    assert rec.gauges["compile_cache_entries"] >= rec.counts["jit_compiles"]
    # no new compiles since: counters stay silent, the gauge persists
    rec2 = _RecorderObs()
    ct.publish_into(rec2)
    assert "jit_compiles" not in rec2.counts
    assert rec2.gauges["compile_cache_entries"] == \
        rec.gauges["compile_cache_entries"]


# -- perf-regression engine -------------------------------------------------

def test_perf_degradation_fires_with_stage_name(tmp_path):
    """A synthetically throttled rate fires ONE attributed warn-only
    event carrying the right series name (and the peer id for fleet
    baselines); the run continues — nothing raises."""
    jsonl = str(tmp_path / "perf.jsonl")
    metrics = Metrics(log_path=jsonl)
    obs = build_obs(ObsConfig(enabled=True, heartbeat_timeout_s=0.0,
                              perf_min_samples=4, perf_cooldown_s=0.0),
                    metrics)
    for _ in range(6):
        obs.perf_rate("grad_steps_per_s", 100.0, step=1)
    obs.perf_rate("grad_steps_per_s", 5.0, step=7)  # throttled stage
    for _ in range(6):
        obs.perf_rate("ingest_rows_per_s", 1000.0, step=1, peer="host-3")
    obs.perf_rate("ingest_rows_per_s", 10.0, step=9, peer="host-3")
    obs.close(9)
    metrics.close()
    recs = [json.loads(line) for line in open(jsonl)]
    events = [r for r in recs if r.get("perf_degradation")]
    local = [e for e in events if e["perf_degradation"]
             == "grad_steps_per_s"]
    assert local, events
    assert local[0].get("perf_peer") is None
    assert local[0]["perf_value"] < local[0]["perf_baseline"]
    peer_ev = [e for e in events if e.get("perf_peer") == "host-3"]
    assert peer_ev and peer_ev[0]["perf_degradation"] == \
        "ingest_rows_per_s"
    # the counter rode the close() publish
    assert any(r.get("ctr/perf_degradations", 0) >= 2 for r in recs)
    # and the offline report lists both with attribution
    from ape_x_dqn_tpu.obs import report
    text = report.format_report(report.summarize(recs))
    assert "perf-degradation events" in text
    assert "peer=host-3" in text


def test_perf_monitor_respects_cooldown_and_min_samples():
    from ape_x_dqn_tpu.obs.profiling import PerfMonitor

    class _M:
        def __init__(self):
            self.records = []

        def log(self, step, **kw):
            self.records.append(kw)

    rec, m = _RecorderObs(), _M()
    mon = PerfMonitor(rec, m, frac=0.5, min_samples=4, cooldown_s=3600.0)
    # below min_samples nothing can fire, however deep the drop
    mon.observe("env_fps", 100.0)
    mon.observe("env_fps", 1.0)
    assert m.records == []
    for _ in range(4):
        mon.observe("env_fps", 100.0)
    mon.observe("env_fps", 1.0)
    assert len(m.records) == 1
    # inside the cooldown a persistent slowdown does not re-fire
    mon.observe("env_fps", 1.0)
    assert len(m.records) == 1


# -- disabled obs stays untouched -------------------------------------------

def test_disabled_obs_emits_nothing_and_never_compiles(tmp_path):
    """The acceptance bar from PR 2 extended to the perf plane: with
    ObsConfig disabled the runtime goes through NullObs, which never
    invokes a stage compile_fn (so no jit is touched, let alone
    re-compiled) and emits no obs records at all."""
    from ape_x_dqn_tpu.runtime.single_process import train_single_process

    assert build_obs(ObsConfig(enabled=False), None) is NULL_OBS
    # stage_attached pretends attached, so drivers skip the (compiling)
    # attach path entirely; an attach called anyway must not compile
    called = []
    assert NULL_OBS.stage_attached("sample_k") is True
    NULL_OBS.stage_attach("sample_k", 4,
                          compile_fn=lambda: called.append(1))
    assert called == []
    with NULL_OBS.stage_window("learn_k", 4):
        pass
    NULL_OBS.perf_rate("env_fps", 100.0)
    assert NULL_OBS.profiler is None and NULL_OBS.perf is None
    # end-to-end: the disabled run's JSONL carries no obs records
    jsonl = str(tmp_path / "off.jsonl")
    metrics = Metrics(log_path=jsonl)
    out = train_single_process(_smoke_cfg(enabled=False),
                               total_env_frames=420, metrics=metrics,
                               train_every=2)
    metrics.close()
    assert out["grad_steps"] > 0
    obs_keys = [k for line in open(jsonl)
                for k in json.loads(line)
                if k.startswith(("gauge/", "ctr/", "hist/", "span/"))]
    assert obs_keys == []
