"""The host threads' own account (PR 52): eight readers over the rows
`obs.trace.SpanTracer.aggregates()` gained — `<span>.cpu`,
`thread.<role>.cpu`, `server.period`, `process.cpu`, `host.gc` — and
their entries. Presence
is asserted, never position: later PRs append."""

import pytest

from benchmarks.harness import cells

# name -> (layer, the end-to-end metric it moves)
METRICS = {
    "server.period_ms": ("inference", "fleet_transitions_per_s"),
    "server.untiled_ms": ("inference", "fleet_transitions_per_s"),
    "server.cpu_ms": ("inference", "fleet_transitions_per_s"),
    "server.stack_cpu_ms": ("inference", "fleet_transitions_per_s"),
    "server.offcpu_ms": ("inference", "fleet_transitions_per_s"),
    "ingest.add_cpu_ms": ("ingest", "fleet_transitions_per_s"),
    "driver.cpu_ms_per_batch": ("drivers", "fleet_transitions_per_s"),
    "driver.gc_pause_ms": ("drivers", "infer_p99_ms"),
}


def _row(count, total_ms):
    return {"count": count, "total_ms": total_ms}


# a window of 100 batches as the kind hands it over: wall rows, the
# `.cpu` rows beside them, the process's clock and two collections
SPANS = {
    "server.period": _row(100, 300.0),
    "server.period.cpu": _row(100, 180.0),
    "server.collect": _row(100, 5.0),
    "server.stack": _row(100, 150.0),
    "server.stack.cpu": _row(100, 70.0),
    "server.dispatch": _row(100, 60.0),
    "server.dispatch.cpu": _row(100, 40.0),
    "server.fetch": _row(100, 20.0),
    "server.fetch.cpu": _row(100, 2.0),
    "server.scatter": _row(100, 30.0),
    "server.scatter.cpu": _row(100, 25.0),
    "replay.add": _row(10, 30.0),
    "replay.add.cpu": _row(10, 12.0),
    "process.cpu": _row(0, 750.0),
    "thread.inference-server.cpu": _row(0, 180.0),
    "thread.ingest.cpu": _row(0, 90.0),
    "host.gc": _row(2, 140.0),
    "host.gc.cpu": _row(2, 139.0),
}
FACTS = {"program_spans": SPANS, "window_s": 0.3,
         "server_window": {"batches": 100, "items": 6400}}
EXPECTED = {
    "server.period_ms": 3.0,
    "server.untiled_ms": (300.0 - 5.0 - 150.0 - 60.0 - 20.0 - 30.0) / 100,
    "server.cpu_ms": 1.8,
    "server.stack_cpu_ms": 0.7,
    "server.offcpu_ms": (80.0 + 20.0 + 5.0) / 100,
    "ingest.add_cpu_ms": 1.2,
    "driver.cpu_ms_per_batch": 7.5,
    "driver.gc_pause_ms": 140.0,
}


def _read(name, facts):
    return cells.layer_metric_reader(name).read(facts)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_metric_is_declared_for_pong_live_alone(name):
    bench = cells.load_benchmark()
    (m,) = [m for m in bench["per_layer"] if m["name"] == name]
    layer, moves = METRICS[name]
    assert m == {"name": name, "unit": "ms", "better": "lower",
                 "source": "program_span", "layer": layer,
                 "moves": moves, "workloads": ["pong_live"]}
    for w in bench["workloads"]:
        reported = {x["name"] for x in cells.resolve(w["name"]).per_layer}
        assert (name in reported) == (w["name"] == "pong_live")


@pytest.mark.parametrize("name", sorted(METRICS))
def test_reader_gives_the_stated_value(name):
    assert _read(name, FACTS) == pytest.approx(EXPECTED[name])


# what the parent hands over: the wall rows and no other
PARENT = {k: v for k, v in SPANS.items()
          if not k.endswith(".cpu") and k not in ("server.period",
                                                  "host.gc")}


@pytest.mark.parametrize("name", sorted(METRICS))
@pytest.mark.parametrize("spans", [None, {}, PARENT])
def test_reader_returns_nothing_without_its_rows(name, spans):
    facts = {**FACTS, "program_spans": spans}
    assert _read(name, facts) is None
    assert _read(name, {}) is None


def test_cpu_per_batch_is_the_whole_process_over_the_windows_batches():
    name = "driver.cpu_ms_per_batch"
    facts = {**FACTS, "server_window": {"batches": 250, "items": 16000}}
    assert _read(name, facts) == pytest.approx(3.0)
    # the serve thread's own rows do not enter it
    lean = {**FACTS, "program_spans": {"process.cpu": _row(0, 750.0)}}
    assert _read(name, lean) == pytest.approx(7.5)
    for window in ({"batches": 0, "items": 0}, None):
        assert _read(name, {**FACTS, "server_window": window}) is None


def test_a_window_without_a_collection_reads_zero_pause():
    spans = {**SPANS, "host.gc": _row(0, 0.0)}
    assert _read("driver.gc_pause_ms",
                 {**FACTS, "program_spans": spans}) == 0.0


def test_a_sampled_cpu_row_is_read_as_a_mean_over_its_own_count():
    """The tracer stamps the CPU clock on one span in `cpu_every`: a
    `.cpu` row's count is then a fraction of its span's."""
    spans = {**SPANS, "server.stack.cpu": _row(10, 7.0),
             "server.period.cpu": _row(25, 45.0),
             "replay.add.cpu": _row(2, 2.4)}
    facts = {**FACTS, "program_spans": spans}
    for name in ("server.stack_cpu_ms", "ingest.add_cpu_ms",
                 "server.offcpu_ms"):
        assert _read(name, facts) == pytest.approx(EXPECTED[name]), name
    # the serve thread's CPU a batch is its clock read whole: however
    # few periods stamped, it is over all of them
    assert _read("server.cpu_ms", facts) == pytest.approx(1.8)


def test_offcpu_needs_both_clocks_of_every_working_span():
    spans = {k: v for k, v in SPANS.items() if k != "server.scatter.cpu"}
    assert _read("server.offcpu_ms",
                 {**FACTS, "program_spans": spans}) is None
