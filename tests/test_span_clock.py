"""The program's spans on the profiler's clock (ISSUE 24): `SpanTracer`
annotations and cross-thread intervals, the allocation-free null path,
the inference server's per-batch spans, the state lock's timed waits,
the learner loop's sampled sync, and `ingest.batch` for loopback
messages."""

import gc
import json
import sys
import threading
import time

import numpy as np
import pytest

from ape_x_dqn_tpu.configs import (
    ActorConfig, InferenceConfig, LearnerConfig, ObsConfig, ReplayConfig,
    get_config)
from ape_x_dqn_tpu.obs.core import NULL_OBS, Obs
from ape_x_dqn_tpu.obs.health import (
    TimedLock, WitnessLock, lock_witness_recorder, make_lock)
from ape_x_dqn_tpu.obs.trace import (
    ANNOTATION_PREFIX, NULL_SPAN, NULL_TRACER, SpanTracer, load_trace)
from ape_x_dqn_tpu.utils.metrics import Metrics

SERVER_SPANS = ("server.collect", "server.queue_wait", "server.stack",
                "server.dispatch", "server.fetch", "server.scatter")


class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: logs enter/exit."""

    log: list = []

    def __init__(self, name, **kwargs):
        self.name = name

    def __enter__(self):
        _FakeAnnotation.log.append(("enter", self.name))

    def __exit__(self, *exc):
        _FakeAnnotation.log.append(("exit", self.name))


@pytest.fixture
def annotations(monkeypatch):
    import jax.profiler

    _FakeAnnotation.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    # a collection under a live tracer is a span of its own,
    # `apex.host.gc` (ISSUE 52): none may start while a test compares
    # the whole log
    was_enabled = gc.isenabled()
    gc.disable()
    yield _FakeAnnotation.log
    if was_enabled:
        gc.enable()


def _traced_obs(tmp_path, **kw) -> Obs:
    return Obs(ObsConfig(enabled=True, blackbox=False,
                         heartbeat_timeout_s=0.0,
                         trace_path=str(tmp_path / "spans.json"), **kw),
               Metrics())


# -- tracer ----------------------------------------------------------------

def test_span_opens_prefixed_annotation_and_folds_bare_name(
        tmp_path, annotations):
    tracer = SpanTracer(str(tmp_path / "t.json"))
    with tracer.span("server.stack", batch=7):
        assert annotations == [("enter", "apex.server.stack")]
    assert annotations == [("enter", "apex.server.stack"),
                           ("exit", "apex.server.stack")]
    assert ANNOTATION_PREFIX == "apex."
    agg = tracer.aggregates()
    assert set(agg) == {"server.stack", "server.stack.cpu", "process.cpu",
                        "thread.MainThread.cpu"}
    assert agg["server.stack"]["count"] == 1
    tracer.close()
    ev = [e for e in load_trace(str(tmp_path / "t.json"))["traceEvents"]
          if e.get("ph") == "X"]
    assert [e["name"] for e in ev] == ["server.stack"]
    assert ev[0]["args"].pop("cpu_us") >= 0.0
    assert ev[0]["args"] == {"batch": 7}


def test_span_records_and_closes_annotation_when_the_body_raises(
        tmp_path, annotations):
    tracer = SpanTracer(str(tmp_path / "t.json"))
    with pytest.raises(KeyError):
        with tracer.span("replay.add"):
            raise KeyError("boom")
    assert annotations[-1] == ("exit", "apex.replay.add")
    assert tracer.aggregates()["replay.add"]["count"] == 1


def test_record_folds_a_cross_thread_interval_without_annotation(
        tmp_path, annotations):
    tracer = SpanTracer(str(tmp_path / "t.json"))
    stamps = {}

    def producer():
        stamps["t0"] = time.perf_counter()

    t = threading.Thread(target=producer)
    t.start()
    t.join()
    t1 = stamps["t0"] + 0.25
    tracer.record("server.queue_wait", stamps["t0"], t1, batch=3)
    tracer.record("server.queue_wait", stamps["t0"], t1 + 0.25, batch=3)
    assert annotations == []
    agg = tracer.aggregates()["server.queue_wait"]
    assert agg["count"] == 2
    assert agg["total_s"] == pytest.approx(0.75)
    assert agg["max_s"] == pytest.approx(0.5)
    tracer.close()
    ev = [e for e in load_trace(str(tmp_path / "t.json"))["traceEvents"]
          if e.get("ph") == "X"]
    assert [round(e["dur"]) for e in ev] == [250_000, 500_000]
    assert all(e["args"] == {"batch": 3} for e in ev)


def test_close_inside_a_collection_leaves_no_annotation_entered(
        annotations, tmp_path):
    """`close()` takes the hook out, so a collection that had started
    never sees its stop: the tracer exits the annotation itself and
    drops the half span."""
    tracer = SpanTracer(str(tmp_path / "t.json"))
    tracer._on_gc("start", {"generation": 1})
    tracer.close()
    assert annotations == [("enter", "apex.host.gc"),
                           ("exit", "apex.host.gc")]
    tracer._on_gc("stop", {"generation": 1})   # a late one finds nothing
    assert "host.gc" not in tracer.aggregates()
    assert len(annotations) == 2


def test_null_path_hands_back_one_shared_object_and_allocates_nothing():
    assert NULL_TRACER.span("a", k=1) is NULL_SPAN
    assert NULL_OBS.span("b", batch=2) is NULL_SPAN
    assert NULL_OBS.stage_window("train", 8) is NULL_SPAN
    assert NULL_TRACER.record("c", 0.0, 1.0, batch=1) is None
    assert NULL_OBS.record("c", 0.0, 1.0) is None
    # a lap (ISSUE 52) opens nothing through either twin
    assert NULL_TRACER.lap("c") is None
    assert NULL_OBS.lap("c", None, batch=1) is None
    with NULL_OBS.span("d") as got:
        assert got is None
    span = NULL_OBS.span
    for _ in range(100):          # warm any lazily-built state
        with span("e"):
            pass
    before = sys.getallocatedblocks()
    for _ in range(10_000):
        with span("e"):
            pass
    assert sys.getallocatedblocks() - before < 50


def test_obs_without_trace_path_spans_are_the_null_span(tmp_path):
    obs = Obs(ObsConfig(enabled=True, blackbox=False,
                        heartbeat_timeout_s=0.0), Metrics())
    assert obs.span("x") is NULL_SPAN and not obs.tracer.enabled
    obs.record("server.queue_wait", 0.0, 1.0)   # no tracer: a no-op
    obs.close()


# -- inference server ------------------------------------------------------

def test_server_batch_yields_six_spans_children_inside_batch(tmp_path):
    from ape_x_dqn_tpu.parallel.inference_server import (
        BatchedInferenceServer)

    obs = _traced_obs(tmp_path)
    server = BatchedInferenceServer(lambda p, x: x * p, np.float32(2.0),
                                    max_batch=6, deadline_ms=2000.0,
                                    obs=obs)
    out: dict = {}

    def client(i):
        x = np.full((2, 3), float(i), np.float32)
        out[i] = np.asarray(server.query_batch(x, 2, timeout=60.0))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    server.stop()
    for i in range(3):
        np.testing.assert_allclose(out[i], 2.0 * i)
    assert server.stats["batches"] == 1   # max_batch reached: one batch
    agg = obs.tracer.aggregates()
    assert set(SERVER_SPANS) | {"server.batch"} <= set(agg)
    assert agg["server.queue_wait"]["count"] == 3      # one per request
    for name in SERVER_SPANS[2:] + ("server.collect", "server.batch"):
        assert agg[name]["count"] == 1, name
    obs.close()
    ev = [e for e in load_trace(str(tmp_path / "spans.json"))
          ["traceEvents"] if e.get("ph") == "X"]
    batch = next(e for e in ev if e["name"] == "server.batch")
    seq = batch["args"]["seq"]
    assert batch["args"]["items"] == 6
    for name in SERVER_SPANS[2:]:
        child = next(e for e in ev if e["name"] == name)
        assert child["args"]["batch"] == seq
        assert batch["ts"] <= child["ts"]
        assert child["ts"] + child["dur"] <= batch["ts"] + batch["dur"] + 1
    # collect ends where the batch begins; every queue wait ends with it
    collect = next(e for e in ev if e["name"] == "server.collect")
    assert collect["args"] == {"batch": seq, "requests": 3}
    assert collect["ts"] + collect["dur"] <= batch["ts"] + 1
    waits = [e for e in ev if e["name"] == "server.queue_wait"]
    assert {e["args"]["batch"] for e in waits} == {seq}
    for e in waits:
        assert e["ts"] + e["dur"] == pytest.approx(
            collect["ts"] + collect["dur"], abs=1.0)


def test_server_without_tracer_takes_the_plain_collect():
    from ape_x_dqn_tpu.parallel.inference_server import (
        BatchedInferenceServer)

    server = BatchedInferenceServer(lambda p, x: x + p, np.float32(1.0),
                                    max_batch=4, deadline_ms=1.0)
    try:
        assert not server._traced
        got = server.query_batch(np.zeros((2, 2), np.float32), 2)
        np.testing.assert_allclose(np.asarray(got), 1.0)
    finally:
        server.stop()


# -- the state lock's waits ------------------------------------------------

class _Holder:
    """ApexDriver's lock plumbing without a driver."""

    from ape_x_dqn_tpu.runtime.driver import ApexDriver
    _hold_state = ApexDriver._hold_state

    def __init__(self, obs):
        self.obs = obs
        self._state_lock = make_lock("driver._state_lock")
        self._time_lock_waits = bool(obs.tracer.enabled)


def test_lock_helper_returns_the_bare_lock_with_obs_off():
    h = _Holder(NULL_OBS)
    assert h._hold_state("ingest") is h._state_lock
    with h._hold_state("learner"):
        assert h._state_lock.locked()
    assert not h._state_lock.locked()


def test_lock_helper_times_the_wait_under_contention(tmp_path):
    obs = _traced_obs(tmp_path)
    h = _Holder(obs)
    held = threading.Event()
    release = threading.Event()

    def hog():
        with h._hold_state("ingest"):
            held.set()
            release.wait(10.0)

    t = threading.Thread(target=hog)
    t.start()
    assert held.wait(10.0)
    threading.Timer(0.2, release.set).start()
    with h._hold_state("learner"):
        assert h._state_lock.locked()
    t.join()
    assert not h._state_lock.locked()
    agg = obs.tracer.aggregates()
    assert agg["state_lock.wait.learner"]["count"] == 1
    assert agg["state_lock.wait.learner"]["total_s"] > 0.1
    # the uncontended acquisition is a span too, of next to nothing
    assert agg["state_lock.wait.ingest"]["count"] == 1
    assert agg["state_lock.wait.ingest"]["total_s"] < 0.1
    obs.close()


def test_timed_lock_keeps_the_witness_order(monkeypatch, tmp_path):
    """Under APEX_LOCK_WITNESS the timed acquisition still reports to
    the order recorder, raises nothing for a consistent order, and
    leaves the thread's held set empty."""
    monkeypatch.setenv("APEX_LOCK_WITNESS", "1")
    obs = _traced_obs(tmp_path)
    h = _Holder(obs)
    assert isinstance(h._state_lock, WitnessLock)
    inner = make_lock("test_span_clock.inner")
    recorder = lock_witness_recorder()
    for who in ("ingest", "learner", "publish"):
        with h._hold_state(who):
            assert "driver._state_lock" in recorder._held()
            with inner:
                pass
    assert "driver._state_lock" not in recorder._held()
    with TimedLock(inner, obs.span("state_lock.wait.test")):
        assert inner.locked()
    assert not inner.locked()
    obs.close()


# -- the driver's loops ----------------------------------------------------

def _tiny_driver_run(tmp_path, monkeypatch, **obs_kw):
    """A cartpole ApexDriver run with tracing on; -> (aggregates,
    trace events, [(thread name, holds _state_lock)] per
    jax.block_until_ready call made while the loops ran)."""
    import jax

    from ape_x_dqn_tpu.runtime.driver import ApexDriver

    monkeypatch.setenv("APEX_LOCK_WITNESS", "1")
    cfg = get_config("cartpole_smoke").replace(
        actors=ActorConfig(num_actors=1, base_eps=0.6, ingest_batch=16),
        replay=ReplayConfig(kind="prioritized", capacity=2048,
                            min_fill=64),
        learner=LearnerConfig(batch_size=32, n_step=3,
                              target_sync_every=100, publish_every=8,
                              train_chunk=2),
        inference=InferenceConfig(max_batch=8, deadline_ms=1.0),
        eval_every_steps=0, eval_episodes=0,
        obs=ObsConfig(enabled=True, blackbox=False,
                      trace_path=str(tmp_path / "spans.json"),
                      **obs_kw))
    driver = ApexDriver(cfg, metrics=Metrics())
    assert isinstance(driver._state_lock, WitnessLock)
    syncs: list = []
    real = jax.block_until_ready
    recorder = lock_witness_recorder()

    def spying(x):
        syncs.append((threading.current_thread().name,
                      "driver._state_lock" in recorder._held()))
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", spying)
    summary = driver.run(total_env_frames=100_000, max_grad_steps=24,
                         wall_clock_limit_s=240)
    monkeypatch.setattr(jax, "block_until_ready", real)
    assert summary["grad_steps"] >= 24 and not summary["loop_errors"]
    agg = driver.obs.tracer.aggregates()
    events = [e for e in load_trace(str(tmp_path / "spans.json"))
              ["traceEvents"] if e.get("ph") == "X"]
    return agg, events, syncs


@pytest.fixture(scope="module")
def default_run(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    try:
        yield _tiny_driver_run(tmp_path_factory.mktemp("default"), mp)
    finally:
        mp.undo()


def test_learner_loop_never_syncs_under_the_state_lock(default_run):
    agg, _, syncs = default_run
    assert agg["learner.train"]["count"] >= 12
    assert not [s for s in syncs if s[1]], syncs
    # with profile_windows off the learner thread does not sync at all
    assert not [s for s in syncs if s[0] == "learner"], syncs


def test_hot_acquisitions_record_their_waits(default_run):
    agg, _, _ = default_run
    assert agg["state_lock.wait.learner"]["count"] == \
        agg["learner.train"]["count"]
    assert agg["state_lock.wait.ingest"]["count"] == \
        agg["replay.add"]["count"] > 0
    assert agg["state_lock.wait.publish"]["count"] == \
        agg["learner.publish_params"]["count"] > 0


def test_unstamped_loopback_message_gets_an_ingest_batch_span(
        default_run):
    agg, events, _ = default_run
    batches = [e for e in events if e["name"] == "ingest.batch"]
    assert batches and agg["ingest.batch"]["count"] >= len(batches)
    for e in batches:
        assert "batch_id" not in e["args"] and "peer" not in e["args"]
        assert e["args"]["rows"] > 0


def test_sampled_window_syncs_after_the_lock_is_released(
        tmp_path, monkeypatch):
    _, _, syncs = _tiny_driver_run(tmp_path, monkeypatch,
                                   profile_windows=True,
                                   profile_window_every=2)
    learner = [s for s in syncs if s[0] == "learner"]
    assert learner, syncs             # every second dispatch is sampled
    assert not [s for s in syncs if s[1]], syncs
