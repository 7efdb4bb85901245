"""`cycle_scopes` and its six readers on a small recorded trace.
data/cycle_probe.xplane.pb (749 kB) was recorded on the v5e in PR 35
from four calls of a learner-shaped `train_many` - a `lax.scan` of four
steps over a 2^8-leaf sum-tree, a [256, 256] uint8 store, 64 draws and a
256 x 256 net - jitted with the nine names: each `cycle.*` scope around
its stage, the descent inside `cycle.sample`, the tree update inside
`cycle.write_back`, the rng split under none; and two calls of an
ingest-shaped `add` whose tree update is under `sum_tree.update` alone.
Every stage's results pass a `jax.lax.optimization_barrier` and the
health norm reads the |TD|s, not the gradient: without either XLA:TPU
fused the optimizer's subtraction into the health norm's reduce (two
consumers of one array make ONE fusion with ONE name) and the trace held
no op under `cycle.optimizer` - what happens to Adam in the pixel cells
(PERF.md section 6, PR 35)."""

import os

import pytest

from benchmarks.harness import cells, cycle_scopes, scope_stats, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PROBE = os.path.join(DATA, "cycle_probe.xplane.pb")
# recorded by PR 26 from a program with other scopes: a parent's trace
PARENT = os.path.join(DATA, "scope_probe.xplane.pb")
READERS = {"replay.sample_share": "cycle.sample",
           "replay.write_back_share": "cycle.write_back",
           "learner.loss_grad_share": "cycle.loss_grad",
           "learner.optimizer_share": "cycle.optimizer",
           "learner.health_share": "cycle.health",
           "learner.cycle_unscoped_share": cycle_scopes.UNSCOPED}
CNN = ["pong_offline", "atari57_dp4_offline", "r2d2_offline"]
OFFLINE = CNN + ["glm47_flash_offline", "trinity_mini_offline"]


def facts_of(path: str) -> dict:
    class Rt:
        @staticmethod
        def newest_xplane():
            return path

    return {"runtime": Rt, "trace": trace_reduce.reduce(path)}


def test_the_names_are_the_programs():
    from ape_x_dqn_tpu.ops import sum_tree
    from ape_x_dqn_tpu.runtime.learner import CYCLE_SCOPES

    assert cycle_scopes.TOP_LEVEL == CYCLE_SCOPES
    assert cycle_scopes.NESTED == (sum_tree.DESCENT_SCOPE,
                                   sum_tree.UPDATE_SCOPE)


def test_the_account_closes_on_the_recorded_trace():
    table = scope_stats.scope_times(PROBE, cycle_scopes.SCOPES)
    assert table == {
        "cycle.sample": 120382, "cycle.batch": 573,
        "cycle.loss_grad": 24545, "cycle.optimizer": 22679,
        "cycle.target_sync": 11422, "cycle.health": 42199,
        "cycle.write_back": 221103, "sum_tree.descent": 112408,
        "sum_tree.update": 250051}
    busy = trace_reduce.reduce(PROBE)["devices"][0]["busy_ns"]
    top = sum(table[s] for s in cycle_scopes.TOP_LEVEL)
    # disjoint: the seven never count an op twice
    assert top <= busy
    # the descent is part of the draw; the tree update is the learner's
    # write-back plus the two ingest adds, which no `cycle.*` name holds
    assert table["sum_tree.descent"] < table["cycle.sample"]
    assert table["sum_tree.update"] > table["cycle.write_back"] * 0.99
    shares = cycle_scopes.shares(table, trace_reduce.reduce(PROBE))
    assert shares[cycle_scopes.UNSCOPED] == pytest.approx(
        100.0 * (busy - top) / busy)
    assert sum(shares[s] for s in cycle_scopes.TOP_LEVEL) + \
        shares[cycle_scopes.UNSCOPED] == pytest.approx(100.0)
    # the residual is real: the adds, the rng split, the loop's own time
    assert 0.0 < shares[cycle_scopes.UNSCOPED] < 50.0


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_on_the_recorded_trace_and_at_the_parent(metric):
    read = cells.layer_metric_reader(metric).read
    facts = facts_of(PROBE)
    want = cycle_scopes.of(facts)[READERS[metric]]
    assert read(facts) == want and 0.0 < want < 100.0
    # one pass per result line: the table is kept in `facts`
    assert "cycle_scope_share" in facts
    # a program without the scopes: nothing, and the residual is not 100
    parent = facts_of(PARENT)
    assert read(parent) is None
    assert parent["cycle_scope_share"] == {}


def test_no_account_without_a_top_level_scope():
    trace = {"devices": [{"busy_ns": 1000, "op_ns": {
        "ragged-dot-none.4 [custom-call]": 50,
        "fusion.7 [custom-call] x.py:1": 30}}]}
    only_nested = {s: 0 for s in cycle_scopes.SCOPES}
    only_nested["sum_tree.update"] = 400      # ingest adds alone
    assert cycle_scopes.shares(only_nested, trace) == {}
    assert cycle_scopes.shares({}, trace) == {}
    table = dict(only_nested, **{"cycle.loss_grad": 700,
                                 "cycle.write_back": 100})
    shares = cycle_scopes.shares(table, trace)
    # the grouped-matmul kernels carry no name stack: found by name
    assert shares["cycle.loss_grad"] == 75.0
    assert shares["cycle.sample"] == 0.0
    assert shares[cycle_scopes.UNSCOPED] == pytest.approx(15.0)
    # a scope the program opens around no op reads as nothing
    facts = {"cycle_scope_share": shares}
    assert cycle_scopes.share_of_busy(facts, "cycle.sample") is None
    assert cycle_scopes.share_of_busy(facts, "cycle.loss_grad") == 75.0


def test_the_six_metrics_are_appended_with_their_cells():
    bench = cells.load_benchmark()
    # appended: the 34 entries of PRs 22-32 come first, unchanged
    new = bench["per_layer"][34:40]
    assert [m["name"] for m in new] == [
        "replay.sample_share", "replay.write_back_share",
        "learner.loss_grad_share", "learner.optimizer_share",
        "learner.health_share", "learner.cycle_unscoped_share"]
    for m in new:
        assert m["unit"] == "%" and m["source"] == "device_trace"
        assert m["moves"] == "learn_samples_per_s"
        assert m["layer"] == m["name"].split(".")[0]
        assert m["better"] == ("higher" if m["name"]
                               == "learner.loss_grad_share" else "lower")
        assert m["workloads"] == (CNN if m["layer"] == "replay"
                                  else OFFLINE)
    live = {m["name"] for m in cells.resolve("pong_live").per_layer}
    assert not live & set(READERS)
    for cell in OFFLINE:
        got = {m["name"] for m in cells.resolve(cell).per_layer}
        assert (set(READERS) <= got) == (cell in CNN)
        assert {n for n in READERS if n.startswith("learner.")} <= got
