"""`server.in_flight_share` (PR 40): the reader over the counts of the
program spans `server.ahead` and `server.batch`, and its entry."""

import pytest

from benchmarks.harness import cells

NAME = "server.in_flight_share"


def test_reader_counts_batches_ahead_over_batches():
    read = cells.layer_metric_reader(NAME).read
    spans = {"server.batch": {"count": 200, "total_ms": 900.0},
             "server.ahead": {"count": 190, "total_ms": 95.0}}
    assert read({"program_spans": spans}) == pytest.approx(95.0)
    # engaged before the window and never inside it: a reading, 0
    spans["server.ahead"] = {"count": 0, "total_ms": 0.0}
    assert read({"program_spans": spans}) == 0.0


@pytest.mark.parametrize("spans", [
    None, {},
    # the parent of PR 40 has batches and no such span
    {"server.batch": {"count": 200, "total_ms": 900.0}},
    {"server.ahead": {"count": 3, "total_ms": 1.0},
     "server.batch": {"count": 0, "total_ms": 0.0}},
])
def test_reader_returns_nothing_and_does_not_raise(spans):
    read = cells.layer_metric_reader(NAME).read
    assert read({"program_spans": spans}) is None
    assert read({}) is None


def test_the_metric_is_declared_for_pong_live_alone():
    bench = cells.load_benchmark()
    (m,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "program_span", "layer": "inference",
                 "moves": "fleet_transitions_per_s",
                 "workloads": ["pong_live"]}
    for w in bench["workloads"]:
        reported = {x["name"] for x in cells.resolve(w["name"]).per_layer}
        assert (NAME in reported) == (w["name"] == "pong_live")
