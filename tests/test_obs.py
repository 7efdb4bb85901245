"""obs/ layer coverage (ISSUE 2): span tracer, metric registry,
heartbeat watchdog, the obs facade, the report CLI, and the two
integration bars — a single-process catch run with tracing + watchdog
ON producing a loadable Perfetto trace with non-empty staleness
histograms, and a deliberately-stalled actor turning a silent driver
hang into an attributed StallError."""

import json
import subprocess
import sys
import threading

import numpy as np
import pytest

from ape_x_dqn_tpu.configs import (
    ActorConfig, EnvConfig, InferenceConfig, LearnerConfig,
    NetworkConfig, ObsConfig, ReplayConfig, get_config)
from ape_x_dqn_tpu.obs.core import (
    NULL_OBS, Obs, SampleAgeTracker, build_obs)
from ape_x_dqn_tpu.obs.health import (
    HeartbeatRegistry, HeartbeatWatchdog, StallError)
from ape_x_dqn_tpu.obs.registry import (
    Histogram, MetricRegistry, geometric_edges)
from ape_x_dqn_tpu.obs.report import format_report, summarize
from ape_x_dqn_tpu.obs.trace import SpanTracer, load_trace, span_names
from ape_x_dqn_tpu.utils.metrics import Metrics


# -- tracer ----------------------------------------------------------------

def test_tracer_writes_valid_perfetto_json(tmp_path):
    path = str(tmp_path / "trace.json")
    tracer = SpanTracer(path)
    with tracer.span("learner.train", k=4):
        with tracer.span("replay.sample"):
            pass
    tracer.mark("actor.ship", segments=3)

    def worker():
        with tracer.span("actor.step"):
            pass

    t = threading.Thread(target=worker, name="actor-0")
    t.start()
    t.join()
    tracer.close()
    trace = load_trace(path)  # json.load would raise on a broken file
    assert span_names(trace) == {"learner.train", "replay.sample",
                                 "actor.ship", "actor.step"}
    evs = trace["traceEvents"]
    # thread metadata rows name the tracks (Perfetto track labels)
    tnames = {e["args"]["name"] for e in evs if e.get("ph") == "M"}
    assert "actor-0" in tnames
    ship = next(e for e in evs if e["name"] == "actor.ship")
    assert ship["args"]["segments"] == 3 and ship["dur"] <= 2
    # spans nest: the inner sample sits inside the outer train window
    train = next(e for e in evs if e["name"] == "learner.train")
    sample = next(e for e in evs if e["name"] == "replay.sample")
    assert train["ts"] <= sample["ts"]
    assert sample["ts"] + sample["dur"] <= train["ts"] + train["dur"] + 1


def test_tracer_bounded_buffer(tmp_path):
    path = str(tmp_path / "trace.json")
    tracer = SpanTracer(path, max_events=5)
    for _ in range(12):
        with tracer.span("s"):
            pass
    tracer.close()
    trace = load_trace(path)
    spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert len(spans) == 5
    assert trace["otherData"]["dropped_events"] == 7
    # aggregates keep counting past the buffer cap
    assert tracer.aggregates()["s"]["count"] == 12


# -- registry --------------------------------------------------------------

def test_tracer_record_and_span_share_one_aggregate(tmp_path):
    """`record()` (caller-stamped, cross-thread) and `span()` fold into
    the same per-name table and the same JSON timeline."""
    import time

    path = str(tmp_path / "trace.json")
    tracer = SpanTracer(path)
    with tracer.span("server.batch", seq=1):
        t0 = time.perf_counter()
    tracer.record("server.batch", t0, t0 + 0.5, seq=2)
    agg = tracer.aggregates()["server.batch"]
    assert agg["count"] == 2 and agg["max_s"] == pytest.approx(0.5)
    tracer.close()
    evs = [e for e in load_trace(path)["traceEvents"]
           if e.get("ph") == "X"]
    assert [e["args"]["seq"] for e in evs] == [1, 2]
    # both intervals sit on the tracer's own clock: the recorded one
    # starts where the span's body stamped t0
    assert evs[0]["ts"] <= evs[1]["ts"] <= evs[0]["ts"] + evs[0]["dur"] + 1


def test_bounded_buffer_counts_recorded_intervals_too(tmp_path):
    tracer = SpanTracer(str(tmp_path / "t.json"), max_events=3)
    for i in range(5):
        tracer.record("server.queue_wait", float(i), float(i) + 1.0)
    assert tracer.aggregates()["server.queue_wait"]["count"] == 5
    tracer.close()
    trace = load_trace(str(tmp_path / "t.json"))
    assert trace["otherData"]["dropped_events"] == 2


def test_null_facade_spans_are_one_preallocated_object():
    from ape_x_dqn_tpu.obs.trace import NULL_SPAN, NULL_TRACER

    assert NULL_OBS.span("learner.train", k=8) is NULL_SPAN
    assert NULL_OBS.stage_window("ingest", 4) is NULL_SPAN
    assert NULL_TRACER.span("x") is NULL_TRACER.span("y") is NULL_SPAN
    assert NULL_TRACER.aggregates() == {}
    with NULL_OBS.span("x"):
        pass  # enters and exits without a tracer


def test_geometric_edges_span_orders_of_magnitude():
    edges = geometric_edges(1.0, 1e3, per_decade=2)
    assert edges[0] == pytest.approx(1.0)
    assert edges[-1] == pytest.approx(1e3)
    assert len(edges) == 7  # 3 decades x 2 + 1
    assert all(a < b for a, b in zip(edges, edges[1:]))


def test_histogram_observe_and_percentiles():
    h = Histogram("h", edges=(1.0, 10.0, 100.0))
    for v in (0.5, 2.0, 3.0, 50.0):
        h.observe(v)
    h.observe(float("nan"))  # diverged TD must not poison buckets
    h.observe_many(np.array([5.0, 500.0, np.nan]))
    assert h.count == 6
    snap = h.snapshot()
    assert snap["count"] == 6
    assert snap["min"] == 0.5 and snap["max"] == 500.0
    # counts: <=1, (1,10], (10,100], >100
    assert snap["counts"] == [1, 3, 1, 1]
    assert snap["sum"] == pytest.approx(560.5)
    # p50 lands in the (1, 10] bucket -> its upper edge
    assert snap["p50"] == 10.0
    # p99 beyond the last edge degrades to the observed max
    assert snap["p99"] == 500.0
    json.dumps(snap)  # snapshot must be directly JSON-serializable


def test_histogram_scalar_bulk_agree():
    vals = np.concatenate([np.random.default_rng(0).uniform(0.1, 2e5, 500),
                           [0.0, 1e7]])
    a = Histogram("a", geometric_edges())
    b = Histogram("b", geometric_edges())
    for v in vals:
        a.observe(v)
    b.observe_many(vals)
    sa, sb = a.snapshot(), b.snapshot()
    assert sa["counts"] == sb["counts"]
    assert (sa["count"], sa["min"], sa["max"]) == \
        (sb["count"], sb["min"], sb["max"])
    assert sa["sum"] == pytest.approx(sb["sum"])  # accumulation order
    assert (sa["p50"], sa["p90"], sa["p99"]) == \
        (sb["p50"], sb["p90"], sb["p99"])


def test_registry_publish_one_jsonl_record(tmp_path):
    path = str(tmp_path / "m.jsonl")
    metrics = Metrics(log_path=path)
    reg = MetricRegistry()
    reg.counter("adds").inc(3)
    reg.gauge("occupancy").set(128)
    reg.histogram("age", (1.0, 10.0)).observe(4.0)
    reg.publish(metrics, step=7, extra={"span/learner.train":
                                        {"count": 2, "total_s": 0.5}})
    metrics.close()
    rec = json.loads(open(path).read().splitlines()[0])
    assert rec["step"] == 7
    assert rec["ctr/adds"] == 3.0
    assert rec["gauge/occupancy"] == 128.0
    assert rec["hist/age"]["count"] == 1
    assert rec["span/learner.train"]["count"] == 2


# -- heartbeats / watchdog -------------------------------------------------

def test_heartbeat_watchdog_attributes_stalest():
    reg = HeartbeatRegistry()
    reg.register("actor-0", now=0.0)
    reg.register("learner", now=0.0)
    reg.beat("learner", "grad_step 100", now=9.5)
    wd = HeartbeatWatchdog(reg, timeout_s=5.0)
    wd.check(now=4.0)  # nobody stale yet
    with pytest.raises(StallError) as ei:
        wd.check(now=10.0)  # actor-0 silent 10s, learner only 0.5s
    e = ei.value
    assert e.component == "actor-0"
    assert e.staleness_s == pytest.approx(10.0)
    assert "actor-0" in str(e) and "10.0s" in str(e)
    # a cleared (legitimately finished) component is never attributed
    reg.clear("actor-0")
    wd.check(now=10.0)


def test_registered_but_never_beating_component_is_attributed():
    """register() seeds the stamp: a component wedged before its first
    loop iteration still gets named."""
    reg = HeartbeatRegistry()
    reg.register("ingest", now=0.0)
    with pytest.raises(StallError, match="ingest"):
        HeartbeatWatchdog(reg, timeout_s=1.0).check(now=2.0)


# -- facade ----------------------------------------------------------------

def test_null_obs_method_parity():
    """Runtime code calls the facade unconditionally; every public Obs
    method must exist on NullObs (and vice versa) or the disabled path
    diverges from the enabled one."""
    def methods(cls):
        return {n for n in dir(cls)
                if not n.startswith("_") and callable(getattr(cls, n))}

    assert methods(Obs) == methods(type(NULL_OBS))


def test_build_obs_gating(tmp_path):
    metrics = Metrics()
    assert build_obs(None, metrics) is NULL_OBS
    assert build_obs(ObsConfig(enabled=False), metrics) is NULL_OBS
    obs = build_obs(ObsConfig(enabled=True), metrics)
    assert isinstance(obs, Obs) and obs.enabled


def test_sample_age_tracker_skip_to_head():
    """The host mirror must match replay/packing.ring_write_start: a
    block that would cross the ring boundary restarts at slot 0."""
    tr = SampleAgeTracker(capacity=8)
    tr.on_add(6, grad_step=10)   # slots 0..5 @ step 10
    tr.on_add(4, grad_step=20)   # 6+4 > 8: skip to head, slots 0..3 @ 20
    ages = tr.ages(np.array([0, 3, 4, 5]), grad_step=25)
    assert list(ages) == [5, 5, 15, 15]


def test_obs_param_lag_and_publish(tmp_path):
    path = str(tmp_path / "m.jsonl")
    metrics = Metrics(log_path=path)
    obs = build_obs(ObsConfig(enabled=True, heartbeat_timeout_s=0.0),
                    metrics)
    obs.set_learner_step(120)
    obs.on_server_batch(items=16, params_version=100, queue_depth=2)
    obs.observe("td_abs", 0.5)
    obs.count("replay_adds", 64)
    obs.close(120)
    metrics.close()
    recs = [json.loads(l) for l in open(path)]
    final = recs[-1]
    assert final["hist/param_lag_steps"]["count"] == 1
    assert final["hist/param_lag_steps"]["max"] == 20.0
    assert final["hist/server_batch_items"]["count"] == 1
    assert final["ctr/replay_adds"] == 64.0
    assert final["gauge/server_queue_depth"] == 2.0
    # pre-seeded instruments publish even when empty (self-describing
    # stream: a missing key and an empty histogram are different facts)
    assert final["hist/sample_age_steps"]["count"] == 0


# -- report ----------------------------------------------------------------

def _synthetic_records():
    return [
        {"step": 0, "run_name": "t", "version": "0.2.0",
         "sample_chunk": 4, "sample_prefetch": False},
        {"step": 500, "frames": 10_000, "frames_per_s": 950.0,
         "grad_steps_per_s": 120.0, "loss": 0.02,
         "span/learner.train": {"count": 125, "total_s": 3.5,
                                "max_s": 0.2},
         "span/replay.add": {"count": 40, "total_s": 1.0, "max_s": 0.1},
         "hist/sample_age_steps": {
             "count": 1000, "sum": 5e8, "min": 10.0, "max": 900_000.0,
             "edges": [1.0, 1e6], "counts": [0, 990, 10],
             "p50": 1e6, "p90": 1e6, "p99": 1_000_000.0},
         "hist/param_lag_steps": {
             "count": 50, "sum": 500.0, "min": 0.0, "max": 40.0,
             "edges": [1.0, 1e5], "counts": [10, 40, 0],
             "p50": 40.0, "p90": 40.0, "p99": 40.0}},
        {"step": 510, "stall_component": "actor-3",
         "stall_staleness_s": 131.0, "stall_note": "frame 9000"},
    ]


def test_report_summarize_and_format():
    s = summarize(_synthetic_records())
    assert s["header"]["version"] == "0.2.0"
    assert s["throughput"]["grad_steps_per_s"] == 120.0
    assert set(s["spans"]) == {"learner.train", "replay.add"}
    assert s["stalls"] == [{"step": 510, "component": "actor-3",
                            "staleness_s": 131.0, "note": "frame 9000"}]
    text = format_report(s)
    assert "learner.train" in text
    assert "sample_age_steps" in text
    # the unhealthy p99 (beyond HEALTHY's 200k bound) gets flagged
    assert "exceeds healthy" in text
    assert "component=actor-3" in text


def test_report_multichip_section():
    """The dp-scaling records a dp sweep writes (`multichip/dpN/*`
    keys + the top-level virtual_devices flag) regroup into a per-dp
    curve and render as the multichip table, with the below-healthy
    efficiency warn and the virtual-device framing."""
    recs = [
        {"step": 0, "multichip/dp1/grad_steps_per_s": 0.9,
         "multichip/dp1/efficiency": 1.0,
         "multichip/dp1/shard_fill_min": 1.0,
         "multichip/dp1/shard_fill_max": 1.0,
         "multichip/dp1/ingest_rows_per_s": 5000.0},
        {"step": 1, "multichip/dp2/grad_steps_per_s": 0.7,
         "multichip/dp2/efficiency": 0.39,
         "multichip/dp2/shard_fill_min": 0.98,
         "multichip/dp2/shard_fill_max": 1.0,
         "multichip/dp2/mfu_train_dist": 0.012,
         "multichip/dp2/device_ms_train_dist": 45.0,
         "multichip/dp2/ingest_rows_per_s": 4000.0},
        {"step": 2, "virtual_devices": True,
         "gauge/dp_scaling_efficiency": 0.39},
    ]
    s = summarize(recs)
    assert sorted(s["multichip"]) == [1, 2]
    assert s["multichip"][2]["efficiency"] == 0.39
    assert s["virtual_devices"] is True
    text = format_report(s)
    assert "multichip scaling" in text
    assert "virtual devices" in text
    assert "0.39x" in text
    assert "below healthy" in text  # dp=2 efficiency warn fires


def test_report_cold_tier_section_and_thrash_check():
    """The tiered-replay section renders door + disk-rung lines from
    the cold_* instruments, and the bespoke check_violations row fires
    when door drops outrun displacements AND the disk rung did not
    absorb them — but stays quiet once spills keep pace (PR 16)."""
    from ape_x_dqn_tpu.obs.report import check_violations
    rec = {"step": 0,
           "gauge/cold_segments": 12.0, "gauge/cold_bytes": 4096.0,
           "gauge/cold_compression_ratio": 3.1,
           "gauge/cold_disk_segments": 16.0,
           "gauge/cold_disk_transitions": 2048.0,
           "gauge/cold_disk_bytes": 65536.0,
           "ctr/cold_evictions": 100.0, "ctr/cold_recalls": 5.0,
           "ctr/cold_displaced": 10.0, "ctr/cold_dropped": 40.0,
           "ctr/cold_disk_spills": 3.0,
           "ctr/cold_disk_promotions": 2.0,
           "ctr/cold_disk_queue_full": 1.0}
    s = summarize([rec])
    text = format_report(s)
    assert "tiered replay" in text
    assert "disk rung" in text
    assert "spills=3" in text
    assert "door drops outrun displacements" in text  # ⚠ warn line
    viols = check_violations(s)
    assert any("cold_dropped" in v and "thrashing" in v for v in viols)
    # disk rung absorbing the overflow (spills >= drops) clears both
    # the section warning and the check violation
    rec["ctr/cold_disk_spills"] = 64.0
    s2 = summarize([rec])
    assert "door drops outrun" not in format_report(s2)
    assert not any("cold_dropped" in v for v in check_violations(s2))


def test_report_cli_subprocess(tmp_path):
    import os
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = tmp_path / "run.jsonl"
    path.write_text("\n".join(json.dumps(r) for r in _synthetic_records())
                    + "\n{torn tail")
    out = subprocess.run(
        [sys.executable, "-m", "ape_x_dqn_tpu.obs.report", str(path)],
        capture_output=True, text=True, timeout=120, cwd=repo_root)
    assert out.returncode == 0, out.stderr
    assert "stage-time breakdown" in out.stdout
    assert "stall events: 1" in out.stdout
    js = subprocess.run(
        [sys.executable, "-m", "ape_x_dqn_tpu.obs.report", str(path),
         "--json"], capture_output=True, text=True, timeout=120,
        cwd=repo_root)
    assert js.returncode == 0, js.stderr
    assert json.loads(js.stdout)["header"]["run_name"] == "t"


# -- integration: traced single-process run --------------------------------

def test_single_process_catch_traced(tmp_path):
    """Tier-1 acceptance (ISSUE 2): a short catch run with tracing +
    watchdog ON produces a loadable Perfetto trace containing spans for
    every named stage, and non-empty staleness histograms in the
    JSONL."""
    from ape_x_dqn_tpu.runtime.single_process import train_single_process

    trace = str(tmp_path / "trace.json")
    jsonl = str(tmp_path / "run.jsonl")
    cfg = get_config("pong").replace(
        env=EnvConfig(id="catch", kind="synthetic_atari"),
        network=NetworkConfig(kind="nature_cnn", dueling=True,
                              compute_dtype="float32"),
        replay=ReplayConfig(kind="prioritized", capacity=2048,
                            min_fill=300),
        learner=LearnerConfig(batch_size=16, n_step=3,
                              target_sync_every=16, sample_chunk=2),
        obs=ObsConfig(enabled=True, trace_path=trace,
                      publish_every_steps=50, heartbeat_timeout_s=120.0),
    )
    metrics = Metrics(log_path=jsonl)
    out = train_single_process(cfg, total_env_frames=420, metrics=metrics,
                               train_every=2)
    metrics.close()
    assert out["grad_steps"] > 0
    names = span_names(load_trace(trace))
    # write-back and target sync run inside learn_k: device scopes
    # (runtime/learner.py::CYCLE_SCOPES), no host event
    assert names >= {"actor.step", "replay.add", "replay.sample",
                     "learner.learn"}, names
    recs = [json.loads(l) for l in open(jsonl)]
    hists = [r for r in recs if "hist/sample_age_steps" in r]
    assert hists, "no registry snapshot reached the JSONL"
    last = hists[-1]
    assert last["hist/sample_age_steps"]["count"] > 0
    assert last["hist/param_lag_steps"]["count"] > 0
    assert last["hist/td_abs"]["count"] > 0
    # sampled ages are bounded by what was ever written
    assert last["hist/sample_age_steps"]["max"] <= out["grad_steps"]
    # the span aggregates rode along for the offline report
    assert any(k.startswith("span/replay.sample") for k in last)


# -- integration: stalled actor raises, not hangs --------------------------

class _StallingActor:
    """Accepts the real actor constructor signature, then wedges: never
    beats, never ships experience. The driver must convert this into an
    attributed StallError instead of hanging forever."""

    def __init__(self, cfg, index, query_fn, transport, seed=0,
                 episode_callback=None, obs=None):
        self.index = index
        self.frames = 0

    def run(self, max_frames, stop_event=None):
        import time
        while stop_event is None or not stop_event.is_set():
            time.sleep(0.02)
        return self.frames


def test_driver_stalled_actor_raises_attributed(monkeypatch, tmp_path):
    """ISSUE 2 acceptance: with the watchdog enabled, a wedged actor
    produces StallError naming the component and its staleness — and
    the trace/metrics artifacts still get flushed on the crash path."""
    from ape_x_dqn_tpu.runtime.driver import ApexDriver

    monkeypatch.setattr("ape_x_dqn_tpu.runtime.family.Actor",
                        _StallingActor)
    trace = str(tmp_path / "trace.json")
    jsonl = str(tmp_path / "m.jsonl")
    cfg = get_config("cartpole_smoke").replace(
        # supervise=False: this test pins the legacy fatal path (wedged
        # actor -> attributed StallError). With supervision on (the
        # default) the supervisor restarts then quarantines the slot
        # instead of raising — that path is tests/test_chaos.py's.
        actors=ActorConfig(num_actors=1, base_eps=0.6, ingest_batch=16,
                           supervise=False),
        replay=ReplayConfig(kind="prioritized", capacity=2048,
                            min_fill=64),
        learner=LearnerConfig(batch_size=32, n_step=3,
                              target_sync_every=100, publish_every=20),
        inference=InferenceConfig(max_batch=8, deadline_ms=1.0),
        obs=ObsConfig(enabled=True, trace_path=trace,
                      heartbeat_timeout_s=1.5),
    )
    driver = ApexDriver(cfg, metrics=Metrics(log_path=jsonl))
    with pytest.raises(StallError) as ei:
        driver.run(total_env_frames=600, max_grad_steps=30,
                   wall_clock_limit_s=120)
    e = ei.value
    assert e.component == "actor-0", e.component
    assert e.staleness_s >= 1.5
    # crash-path artifacts: the stall rode the JSONL and the trace flushed
    recs = [json.loads(l) for l in open(jsonl)]
    stall = [r for r in recs if r.get("stall_component")]
    assert stall and stall[-1]["stall_component"] == "actor-0"
    load_trace(trace)  # valid JSON even on the crash path
