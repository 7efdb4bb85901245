"""The readers of the program's own spans (PR 24): nine one-expression
readers over `facts["program_spans"]`, and `device.idle_in_serve_host`,
which lays the `apex.*` host annotations of the xplane over the idle
gaps `trace_reduce` found. The recorded trace is 100 ms of a traced
`pong_live` run on one v5e chip (PR 24), trimmed like the PR 22 one
beside it (the device's `XLA Ops`/`XLA Modules` lines, the harness's
`bench.*` and the program's `apex.*` host annotations); the numbers
below were read off it once."""

import json
import os

import pytest

from benchmarks.harness import cells, program_spans, span_stats
from benchmarks.harness import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACE = os.path.join(DATA, "pong_live_spans_100ms.xplane.pb")
OLD_TRACE = os.path.join(DATA, "pong_live_100ms.xplane.pb")

SPAN_READERS = {
    "server.queue_wait_ms": "server.queue_wait",
    "server.collect_ms": "server.collect",
    "server.stack_ms": "server.stack",
    "server.dispatch_ms": "server.dispatch",
    "server.fetch_ms": "server.fetch",
    "server.scatter_ms": "server.scatter",
    "ingest.batch_ms": "ingest.batch",
    "ingest.lock_wait_ms": "state_lock.wait.ingest",
    "learner.lock_wait_ms": "state_lock.wait.learner",
}
NEW_METRICS = tuple(SPAN_READERS) + ("device.idle_in_serve_host",)


class _Runtime:
    def __init__(self, path):
        self._path = path

    def newest_xplane(self):
        return self._path


def test_mean_ms_present_absent_and_empty():
    facts = {"program_spans": {"a": {"count": 4, "total_ms": 10.0},
                               "b": {"count": 0, "total_ms": 0.0}}}
    assert span_stats.mean_ms(facts, "a") == 2.5
    assert span_stats.mean_ms(facts, "b") is None
    assert span_stats.mean_ms(facts, "c") is None
    assert span_stats.mean_ms({}, "a") is None
    assert span_stats.mean_ms({"program_spans": None}, "a") is None


@pytest.mark.parametrize("metric,span", sorted(SPAN_READERS.items()))
def test_span_reader_present_and_absent(metric, span):
    read = cells.layer_metric_reader(metric).read
    facts = {"program_spans": {span: {"count": 8, "total_ms": 20.0},
                               "other": {"count": 1, "total_ms": 1.0}}}
    assert read(facts) == 2.5
    # the parent of PR 24 has no such span: nothing, and no raise
    assert read({"program_spans": {"other": {"count": 1,
                                             "total_ms": 1.0}}}) is None
    assert read({}) is None


def test_the_ten_metrics_are_declared_for_pong_live_only():
    bench = cells.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    # appended: the thirteen PR 22 entries come first, unchanged
    assert [m["name"] for m in bench["per_layer"]][13:] == \
        list(NEW_METRICS)
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["workloads"] == ["pong_live"] and m["better"] == "lower"
        assert m["source"] == ("device_trace" if name.startswith(
            "device.") else "program_span")
    assert by_name["server.queue_wait_ms"]["moves"] == "infer_p99_ms"
    live = {m["name"] for m in cells.resolve("pong_live").per_layer}
    assert set(NEW_METRICS) <= live
    for other in ("pong_offline", "atari57_dp4_offline"):
        assert not set(NEW_METRICS) & {
            m["name"] for m in cells.resolve(other).per_layer}


def test_intersect_of_sorted_disjoint_intervals():
    a = [(0, 10), (20, 30), (40, 50)]
    b = [(5, 25), (28, 45), (60, 70)]
    assert program_spans._intersect(a, b) == [
        (5, 10), (20, 25), (28, 30), (40, 45)]
    assert program_spans._intersect(a, []) == []
    assert program_spans._intersect([(0, 5)], [(5, 9)]) == []


def test_idle_by_span_on_hand_made_gaps(monkeypatch):
    trace = {"worst_plane": "/device:TPU:1",
             "devices": [{"plane": "/device:TPU:0", "gaps": [(0, 1)]},
                         {"plane": "/device:TPU:1",
                          "gaps": [(0, 100), (200, 300)]}]}
    spans = {"server.stack": [(10, 30)],
             "server.dispatch": [(30, 40), (250, 260)],
             "server.scatter": [(90, 120)],            # half in a gap
             "server.batch": [(10, 120), (240, 270)],
             "server.collect": [(120, 240), (270, 400)],
             "replay.add": [(20, 35), (22, 50)]}       # two threads
    monkeypatch.setattr(program_spans, "host_spans", lambda path: spans)
    t = program_spans.idle_by_span(trace, "ignored")
    assert t["plane"] == "/device:TPU:1"
    assert t["idle_s"] == pytest.approx(200e-9)
    rows = {name: ns for name, ns, _ in t["by_span"]}
    assert rows["server.batch"] == pytest.approx((90 + 30) * 1e-9)
    assert rows["server.collect"] == pytest.approx((40 + 30) * 1e-9)
    assert rows["replay.add"] == pytest.approx(30e-9)   # union, not sum
    assert [r[0] for r in t["by_span"]][0] == "server.batch"
    # stack 20 + dispatch 10 + 10 + scatter 10 of 200 idle
    assert t["serve_host_s"] == pytest.approx(50e-9)
    assert t["serve_host_share"] == pytest.approx(0.25)
    # (0,10) is under no span
    assert t["uncovered_s"] == pytest.approx(10e-9)


def test_a_trace_without_program_spans_reads_as_nothing():
    """PR 22's recorded trace predates the `apex.*` annotations, as
    any run of the parent does: the reader returns None."""
    assert program_spans.host_spans(OLD_TRACE) == {}
    tr = trace_reduce.reduce(OLD_TRACE)
    assert program_spans.idle_by_span(tr, OLD_TRACE) is None
    facts = {"trace": tr, "runtime": _Runtime(OLD_TRACE)}
    read = cells.layer_metric_reader("device.idle_in_serve_host").read
    assert read(facts) is None
    assert read({"trace": None, "runtime": _Runtime(None)}) is None


# -- the recorded PR 24 trace ----------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    assert os.path.getsize(TRACE) < 1 << 20
    tr = trace_reduce.reduce(TRACE)
    return tr, program_spans.idle_by_span(tr, TRACE)


def test_recorded_trace_holds_the_program_spans(recorded):
    spans = program_spans.host_spans(TRACE)
    # seven served batches, the learner's one dispatch and one add_many
    assert {k: len(v) for k, v in spans.items()} == {
        "ingest.batch": 29, "replay.add": 1, "server.batch": 8,
        "server.fetch": 8, "server.scatter": 7, "server.collect": 7,
        "server.stack": 7, "server.dispatch": 7,
        "state_lock.wait.learner": 1, "learner.train": 1}
    with open(os.path.join(DATA, "pong_live_spans_100ms.json")) as fh:
        assert json.load(fh)["events"] == {k: len(v)
                                           for k, v in spans.items()}


def test_recorded_idle_in_serve_host_and_table(recorded, capsys):
    tr, table = recorded
    assert tr["window_s"] == pytest.approx(0.1)
    mods = tr["devices"][0]["modules"]
    assert {k: v["count"] for k, v in mods.items()} == {
        "jit_add_many": 1, "jit__lambda": 6, "jit_train_many": 1}
    assert tr["idle_share_worst"] == pytest.approx(0.80218316, abs=1e-8)
    assert table["plane"] == "/device:TPU:0"
    assert table["idle_s"] == pytest.approx(0.080198131, abs=1e-9)
    assert table["serve_host_share"] == pytest.approx(0.15398385,
                                                      abs=1e-8)
    assert table["uncovered_s"] == pytest.approx(0.0015696, abs=1e-9)
    # longest first; in a profiler session the chip idles under
    # server.fetch, not under the serve thread's own host work
    assert [row[0] for row in table["by_span"]][:4] == [
        "server.batch", "server.fetch", "server.stack", "learner.train"]
    assert table["by_span"][1][1] == pytest.approx(0.065811954, abs=1e-9)
    with open(os.path.join(DATA, "pong_live_spans_100ms.json")) as fh:
        want = json.load(fh)
    assert [[n, round(s, 9), c] for n, s, c in table["by_span"]] == \
        want["by_span"]
    facts = {"trace": tr, "runtime": _Runtime(TRACE)}
    read = cells.layer_metric_reader("device.idle_in_serve_host").read
    assert read(facts) == pytest.approx(15.398385, abs=1e-6)
    assert "idle by program span" in capsys.readouterr().err
