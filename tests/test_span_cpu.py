"""The host threads account for themselves (ISSUE 52): a thread-CPU
clock beside the wall clock in the spans, `<name>.cpu`,
`thread.<role>.cpu` and `process.cpu` rows in the aggregates, the collector's pauses as `host.gc`,
the serve loop's whole period as `server.period` — and none of it on a
path that runs with obs off."""

import gc
import inspect
import threading
import time

import numpy as np
import pytest

from ape_x_dqn_tpu.configs import ObsConfig
from ape_x_dqn_tpu.obs import report, trace
from ape_x_dqn_tpu.obs.core import NULL_OBS, NullObs, Obs
from ape_x_dqn_tpu.obs.trace import (
    NULL_TRACER, NullTracer, SpanTracer, load_trace)
from ape_x_dqn_tpu.parallel.inference_server import BatchedInferenceServer
from ape_x_dqn_tpu.utils.metrics import Metrics

PERIOD_CHILDREN = ("server.collect", "server.stack", "server.dispatch",
                   "server.fetch", "server.scatter")


def _burn(cpu_s: float) -> float:
    """Spin until this thread has used `cpu_s` of CPU; what it used."""
    c0 = time.thread_time()
    while time.thread_time() - c0 < cpu_s:
        sum(range(1000))
    return time.thread_time() - c0


def _hooks() -> list:
    """`gc.callbacks` as found, after a collection: an earlier test's
    tracer that was dropped unclosed gives its hook up now, not in the
    middle of a comparison."""
    gc.collect()
    return list(gc.callbacks)


def _events(path) -> list[dict]:
    return [e for e in load_trace(str(path))["traceEvents"]
            if e.get("ph") == "X"]


def _traced_obs(tmp_path) -> Obs:
    return Obs(ObsConfig(enabled=True, blackbox=False,
                         heartbeat_timeout_s=0.0,
                         trace_path=str(tmp_path / "spans.json")),
               Metrics())


def _tracer(tmp_path) -> SpanTracer:
    return SpanTracer(str(tmp_path / "t.json"))


@pytest.fixture
def every_span(monkeypatch):
    """Every span stamps the CPU clock (the tracer samples it in time:
    `trace.CPU_EVERY_S`)."""
    monkeypatch.setattr(trace, "CPU_EVERY_S", 0.0)


# -- the second clock ------------------------------------------------------

# the CPU stamps lie outside the wall stamps, so that a span's wall
# extent is what it is without them: a span's CPU can pass its wall time
# by the two stamps and the annotation between them
STAMPS_S = 1e-3


def test_a_busy_span_reads_its_cpu_and_a_sleeping_one_next_to_none(
        tmp_path, every_span):
    tracer = _tracer(tmp_path)
    with tracer.span("busy"):
        burned = _burn(0.05)
    with tracer.span("asleep"):
        time.sleep(0.05)
    agg = tracer.aggregates()
    busy, asleep = agg["busy.cpu"], agg["asleep.cpu"]
    # the span's stamps enclose the loop's own: at least what the loop
    # burned, the stamps' worth more (how close CPU comes to wall is the
    # machine's load, not the tracer's doing, so it is not asserted)
    assert burned <= busy["total_s"] <= burned + 2e-3
    assert busy["total_s"] <= agg["busy"]["total_s"] + STAMPS_S
    assert agg["asleep"]["total_s"] >= 0.05 and asleep["total_s"] < 5e-3
    assert busy == {"count": 1, "total_s": busy["total_s"],
                    "max_s": busy["total_s"]}
    # one number, one place: the wall row has the wall clock's columns
    assert set(agg["busy"]) == {"count", "total_s", "max_s"}
    tracer.close()
    ev = {e["name"]: e for e in _events(tmp_path / "t.json")}
    assert ev["busy"]["args"]["cpu_us"] == pytest.approx(
        busy["total_s"] * 1e6)
    assert ev["asleep"]["args"]["cpu_us"] < 5e3 <= 50e3 <= ev[
        "asleep"]["dur"]


def _span_thrice(tracer):
    for _ in range(3):
        with tracer.span("x", k=1):
            pass


def _record_across_threads(tracer):
    t0 = time.perf_counter()
    t = threading.Thread(
        target=lambda: tracer.record("x", t0, time.perf_counter(), b=1))
    t.start()
    t.join()


def _lap_twice(tracer):
    since = tracer.lap("x")   # opens the first, records nothing
    since = tracer.lap("x", since, batch=1)
    tracer.lap("x", since, batch=2)


@pytest.mark.parametrize("fold, count, cpu_count", [
    (_span_thrice, 3, 3),
    (_lap_twice, 2, 2),
    # an interval that crosses threads has no thread to charge, and a
    # mark no duration on either clock
    (_record_across_threads, 1, 0),
    (lambda tracer: tracer.mark("x", rows=4), 1, 0),
    (lambda tracer: tracer.remote_span("x", 0.5, peer="h1"), 1, 0),
])
def test_a_cpu_row_counts_what_its_span_counts_or_is_absent(
        tmp_path, every_span, fold, count, cpu_count):
    tracer = _tracer(tmp_path)
    fold(tracer)
    agg = tracer.aggregates()
    assert agg["x"]["count"] == count
    if cpu_count:
        assert agg["x.cpu"]["count"] == cpu_count == count
        assert 0.0 <= agg["x.cpu"]["max_s"] <= agg["x.cpu"]["total_s"]
    else:
        assert "x.cpu" not in agg
    tracer.close()
    for e in _events(tmp_path / "t.json"):
        assert ("cpu_us" in e.get("args", {})) == bool(cpu_count)


@pytest.mark.parametrize("every_s, gap_s, spans, stamped", [
    # in 64ths of a second, so that the stepped clock adds up exactly
    (0.0, 1 / 64, 9, 9),        # every span
    (4 / 64, 1 / 64, 11, 3),    # a hot name: its rounds 0, 4 and 8
    (4 / 64, 5 / 64, 4, 4),     # a rare name: every one
    (4 / 64, 1 / 64, 1, 1)])    # the first always
def test_a_name_reads_the_cpu_clock_once_in_so_many_seconds(
        tmp_path, monkeypatch, every_s, gap_s, spans, stamped):
    """The clock is a system call with the GIL held (6-10 us under
    gVisor): a span pays for it when its name's last reading is
    `CPU_EVERY_S` old, the others make no call at all, and the `.cpu`
    row counts the former."""
    calls = []
    real_cpu = time.thread_time
    monkeypatch.setattr(time, "thread_time",
                        lambda: calls.append(1) or real_cpu())
    monkeypatch.setattr(trace, "CPU_EVERY_S", every_s)
    now = [100.0]   # the wall clock, stepped by hand

    def stepped():
        now[0] += gap_s / 8   # eight reads a round
        return now[0]

    tracer = _tracer(tmp_path)
    monkeypatch.setattr(time, "perf_counter", stepped)
    for _ in range(spans):
        with tracer.span("x"):   # three reads: is it due, in, out
            pass
        with tracer.span("y"):   # each name keeps its own cadence
            pass
        stepped(), stepped()
    monkeypatch.undo()
    assert len(calls) == 2 * 2 * stamped
    agg = tracer.aggregates()
    assert agg["x"]["count"] == agg["y"]["count"] == spans
    assert agg["x.cpu"]["count"] == agg["y.cpu"]["count"] == stamped
    tracer.close()
    with_cpu = [e for e in _events(tmp_path / "t.json")
                if "cpu_us" in e.get("args", {})]
    assert len(with_cpu) == 2 * stamped


def test_laps_tile_one_threads_time_on_both_clocks(tmp_path, every_span):
    """`lap()` closes the interval the last one opened and opens the
    next where it ends; the first only opens."""
    tracer = _tracer(tmp_path)
    since = tracer.lap("loop")
    assert "loop" not in tracer.aggregates()
    burned = 0.0
    for k in range(3):
        burned += _burn(0.01)
        since = tracer.lap("loop", since, k=k)
    agg = tracer.aggregates()
    assert agg["loop"]["count"] == agg["loop.cpu"]["count"] == 3
    assert burned <= agg["loop.cpu"]["total_s"] <= (
        agg["loop"]["total_s"] + 3 * STAMPS_S)
    assert NULL_TRACER.lap("loop") is None
    assert NULL_OBS.lap("loop", None, k=0) is None
    tracer.close()
    laps = sorted((e for e in _events(tmp_path / "t.json")),
                  key=lambda e: e["ts"])
    assert [e["args"]["k"] for e in laps] == [0, 1, 2]
    for a, b in zip(laps, laps[1:]):
        assert a["ts"] + a["dur"] == pytest.approx(b["ts"], abs=1e-3)


def test_the_default_reads_a_hot_names_cpu_twenty_times_a_second(
        tmp_path):
    assert trace.CPU_EVERY_S == 0.05
    obs = _traced_obs(tmp_path)
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < 0.3:
        with obs.span("x"):
            n += 1
    stamped = obs.tracer.aggregates()["x.cpu"]["count"]
    obs.close()
    # at 0, 0.05, ... 0.30 s; fewer only if the machine held the
    # thread off a core for a quarter of a second
    assert n > 50 and 2 <= stamped <= 7


def test_a_threads_cpu_clock_is_handed_out_by_role(tmp_path):
    """Every stamp is the thread's CPU seconds so far: the latest of
    each thread, summed over the threads of a role (the name less a
    trailing number), brackets that role's CPU between two snapshots —
    at the default cadence, no span stamped for the purpose."""
    tracer = _tracer(tmp_path)

    def worker():
        # names of its own: a name keeps ONE cadence for all the threads
        # that open it, so of two threads inside one 20th of a second
        # only the first would stamp
        _burn(0.03)
        with tracer.span("done." + threading.current_thread().name):
            pass

    with tracer.span("first"):
        pass
    before = tracer.aggregates()
    assert before["thread.MainThread.cpu"]["count"] == 0
    assert "thread.worker.cpu" not in before
    threads = [threading.Thread(target=worker, name=f"worker-{i}")
               for i in (3, 14)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    burned = _burn(0.02)
    with tracer.span("last"):
        pass
    after = tracer.aggregates()
    tracer.close()
    # two threads of one role: both burns are in the one row
    assert after["thread.worker.cpu"]["total_s"] >= 2 * 0.03
    assert after["thread.worker.cpu"]["count"] == 0
    grown = (after["thread.MainThread.cpu"]["total_s"]
             - before["thread.MainThread.cpu"]["total_s"])
    assert burned <= grown
    assert grown <= (after["process.cpu"]["total_s"]
                     - before["process.cpu"]["total_s"])


def test_process_cpu_brackets_a_busy_loop(tmp_path):
    tracer = _tracer(tmp_path)
    before = tracer.aggregates()["process.cpu"]
    burned = _burn(0.05)
    after = tracer.aggregates()["process.cpu"]
    assert before["count"] == after["count"] == 0
    assert after["total_s"] - before["total_s"] >= burned
    assert NULL_TRACER.aggregates() == {}
    tracer.close()


# -- the collector's pauses ------------------------------------------------

def test_a_collection_is_one_host_gc_span_and_close_takes_the_hook_out(
        tmp_path):
    found = _hooks()
    gc.disable()   # only the collection the test asks for
    try:
        tracer = _tracer(tmp_path)
        assert len(gc.callbacks) == len(found) + 1
        gc.collect()
        agg = tracer.aggregates()
        assert agg["host.gc"]["count"] == agg["host.gc.cpu"]["count"] == 1
        assert 0.0 <= agg["host.gc.cpu"]["total_s"] <= (
            agg["host.gc"]["total_s"] + STAMPS_S)
        tracer.close()
        assert gc.callbacks == found
        gc.collect()   # after close(): nobody is listening
        assert tracer.aggregates()["host.gc"]["count"] == 1
    finally:
        gc.enable()
    (ev,) = [e for e in _events(tmp_path / "t.json")
             if e["name"] == "host.gc"]
    assert ev["args"]["generation"] == 2
    assert 0.0 <= ev["args"]["cpu_us"] <= ev["dur"] + STAMPS_S * 1e6


def test_a_collection_inside_the_tracers_lock_does_not_deadlock(tmp_path):
    """The hook fires on whichever thread allocates, also one that is
    inside `_record`: it takes no lock of the tracer's."""
    tracer = _tracer(tmp_path)
    done = []

    def collect_under_lock():
        with tracer._lock:
            gc.collect()
        done.append(True)

    t = threading.Thread(target=collect_under_lock, daemon=True)
    t.start()
    t.join(timeout=30.0)
    assert done, "gc hook waited for the lock its own thread holds"
    assert tracer.aggregates()["host.gc"]["count"] >= 1
    tracer.close()


def test_a_dropped_tracer_takes_its_hook_along_and_null_installs_none(
        tmp_path):
    found = _hooks()
    tracer = _tracer(tmp_path)
    assert len(gc.callbacks) == len(found) + 1
    del tracer   # never closed: the hook holds it weakly
    gc.collect()
    assert gc.callbacks == found
    NullTracer().close()
    with NULL_OBS.span("x"):
        gc.collect()
    assert gc.callbacks == found


# -- the twins -------------------------------------------------------------

def _public(cls) -> dict:
    """Public method -> its parameters (the twins differ, by design,
    in what they hand back)."""
    return {name: list(inspect.signature(fn).parameters.values())
            for name, fn in inspect.getmembers(cls, inspect.isfunction)
            if not name.startswith("_")}


@pytest.mark.parametrize("null, live", [(NullTracer, SpanTracer),
                                        (NullObs, Obs)])
def test_null_twin_keeps_the_live_signatures(null, live):
    null_api, live_api = _public(null), _public(live)
    assert set(live_api) <= set(null_api)
    for name, params in live_api.items():
        assert params == null_api[name], name
    assert null().lap("s", None, batch=1) is None


# -- the report ------------------------------------------------------------

def test_report_prints_cpu_as_a_column_and_sums_wall_rows_only():
    def row(count, total_s, max_s=2.0):
        return {"count": count, "total_s": total_s, "max_s": max_s}

    wall, cpu = report._split_cpu({
        "server.stack": row(2, 3.0),
        "server.stack.cpu": row(1, 0.5, 0.5),   # one in two stamped
        "server.queue_wait": row(1, 1.0, 1.0),
        "process.cpu": row(0, 7.25, 0.0),
        "thread.learner.cpu": row(0, 1.5, 0.0)})
    assert set(wall) == {"server.stack", "server.queue_wait"}
    lines = report._fmt_spans(wall, cpu)
    table = {ln.split()[0]: ln.split() for ln in lines[2:-1]}
    assert "cpu_ms" in lines[1] and set(table) == set(wall)
    # count total_s mean_ms max_ms cpu_ms share: the mean of the spans
    # that stamped the clock, beside the mean wall of all of them; the
    # shares are of the wall rows' sum
    assert table["server.stack"][1:] == ["2", "3.000", "1500.000",
                                         "2000.000", "500.000", "75.0%"]
    assert table["server.queue_wait"][5:] == ["-", "25.0%"]
    assert lines[-1].strip() == ("CPU seconds so far: process 7.250, "
                                 "thread.learner 1.500")
    # a table from before the second clock prints as it did, one column
    # more
    assert len(report._fmt_spans(wall)) == 4


# -- the serve loop's period -----------------------------------------------

def _serve(obs, clients=6, queries=20):
    server = BatchedInferenceServer(lambda p, x: x * p, np.float32(2.0),
                                    max_batch=4, deadline_ms=1.0, obs=obs)
    failures = []

    def client(i):
        x = np.full(3, float(i), np.float32)
        for _ in range(queries):
            got = np.asarray(server.query(x, timeout=60.0))
            if not np.allclose(got, 2.0 * i):
                failures.append((i, got))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    server.stop()
    assert not failures
    return server


def test_periods_are_batches_and_their_children_fit_inside(
        tmp_path, every_span):
    obs = _traced_obs(tmp_path)
    server = _serve(obs)
    batches = server.stats["batches"]
    agg = obs.tracer.aggregates()
    obs.close()
    assert agg["server.period"]["count"] == batches
    assert agg["server.period.cpu"]["count"] == batches
    assert (agg["server.period.cpu"]["total_s"]
            <= agg["server.period"]["total_s"] + batches * STAMPS_S)
    # the serve thread's own clock: all its periods' CPU and more
    assert (agg["thread.inference-server.cpu"]["total_s"]
            >= agg["server.period.cpu"]["total_s"] - batches * STAMPS_S)
    assert "server.collect.cpu" not in agg   # it waits: wall only
    for name in PERIOD_CHILDREN:
        assert agg[name]["count"] == batches
    for name in PERIOD_CHILDREN[1:]:
        assert agg[name + ".cpu"]["count"] == batches
        assert agg[name + ".cpu"]["total_s"] <= (
            agg[name]["total_s"] + batches * STAMPS_S)
    ev = _events(tmp_path / "spans.json")
    periods = [e for e in ev if e["name"] == "server.period"]
    assert sorted(e["args"]["behind"] for e in periods) == list(
        range(1, batches + 1))
    children: dict = {}
    for e in ev:
        if e["name"] in PERIOD_CHILDREN:
            children.setdefault((e["name"], e["args"]["batch"]), e)
    for p in periods:
        # the first half of the batch it dispatched, the second half of
        # the one it answered (one that was left open also holds the
        # first half of the one it answered: its own is then the less)
        mine = [children[name, p["args"]["batch"]]
                for name in PERIOD_CHILDREN[:3] if p["args"]["batch"]]
        mine += [children[name, p["args"]["behind"]]
                 for name in PERIOD_CHILDREN[3:]]
        assert sum(c["dur"] for c in mine) <= p["dur"] + 1
        for c in mine:
            assert p["ts"] <= c["ts"] + 1
            assert c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1
        assert p["args"]["cpu_us"] <= p["dur"] + STAMPS_S * 1e6
    # a period starts where the last one ended or later: they never
    # overlap, so their sum is time of one thread
    periods.sort(key=lambda e: e["ts"])
    for a, b in zip(periods, periods[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1


def test_without_obs_the_serve_thread_reads_no_cpu_clock(monkeypatch):
    real, calls = time.thread_time, []

    def watched():
        name = threading.current_thread().name
        if name == "inference-server":
            calls.append(name)
            raise AssertionError("thread_time on the untraced path")
        return real()

    monkeypatch.setattr(time, "thread_time", watched)
    hooks = _hooks()
    server = _serve(NULL_OBS, clients=3, queries=10)
    assert server.stats["batches"] >= 1 and not calls
    assert gc.callbacks == hooks
