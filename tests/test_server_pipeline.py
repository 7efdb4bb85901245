"""The serve thread keeps one forward in flight (ISSUE 40):
`BatchedInferenceServer` dispatches batch k+1 before it fetches batch
k, but only when k+1's requests are already waiting.

Most cases need two batches in flight at a moment the test chooses. The
device cannot be asked to hold still, the tracer can: `_Gated` is an
`apply_fn` whose FIRST trace — which runs in the serve thread, inside
batch 1's dispatch — waits for the test, so whatever the test enqueues
meanwhile is "already waiting" when the loop looks, and goes ahead."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from ape_x_dqn_tpu.configs import ObsConfig
from ape_x_dqn_tpu.obs.core import NullObs, Obs
from ape_x_dqn_tpu.obs.trace import load_trace
from ape_x_dqn_tpu.parallel.inference_server import BatchedInferenceServer
from ape_x_dqn_tpu.utils.metrics import Metrics

CHILDREN = ("server.stack", "server.dispatch", "server.fetch",
            "server.scatter")


class _Gated:
    def __init__(self, fn=lambda p, x: x * p):
        self.fn = fn
        self.entered = threading.Event()
        self.release = threading.Event()

    def __call__(self, p, x):
        if not self.entered.is_set():
            self.entered.set()
            assert self.release.wait(30.0)
        return self.fn(p, x)


class _CountingObs(NullObs):
    """`on_server_batch` as the server calls it: (items, version)."""

    def __init__(self):
        self.served: list[tuple[int, int]] = []

    def on_server_batch(self, items, params_version, queue_depth):
        self.served.append((items, params_version))


def _traced_obs(tmp_path) -> Obs:
    return Obs(ObsConfig(enabled=True, blackbox=False,
                         heartbeat_timeout_s=0.0,
                         trace_path=str(tmp_path / "spans.json")),
               Metrics())


def _rows(value: float, n: int) -> np.ndarray:
    return np.full((n, 3), value, np.float32)


def _ask(server, x, n, out: dict, key) -> threading.Thread:
    """`query_batch` on a thread of its own; the reply, or the error it
    raised, lands in out[key]."""
    def run():
        try:
            out[key] = np.asarray(server.query_batch(x, n, timeout=30.0))
        except Exception as e:  # noqa: BLE001 - the test reads it
            out[key] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t


def _wait(cond, what: str) -> None:
    deadline = time.monotonic() + 30.0
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.001)


def _enqueue_behind(server, gate, asks: list, out: dict) -> list:
    """With batch 1 held inside its dispatch, put `asks` ((x, n, key),
    in this order) on the queue, then let batch 1 go."""
    assert gate.entered.wait(30.0)
    threads = []
    for i, (x, n, key) in enumerate(asks):
        threads.append(_ask(server, x, n, out, key))
        _wait(lambda: server._q.qsize() == i + 1, "request not queued")
    gate.release.set()
    return threads


def _joined(threads) -> None:
    for t in threads:
        t.join(timeout=30.0)
        assert not t.is_alive()


def _events(tmp_path) -> list[dict]:
    return [e for e in load_trace(str(tmp_path / "spans.json"))
            ["traceEvents"] if e.get("ph") == "X"]


def test_every_row_right_under_eight_threads_and_replies_in_batch_order(
        tmp_path):
    obs = _traced_obs(tmp_path)

    def apply_fn(p, x):
        return jnp.tanh(x * p["w"]) + p["b"]

    params = {"w": np.float32(0.5), "b": np.float32(-1.0)}
    server = BatchedInferenceServer(apply_fn, params, max_batch=6,
                                    deadline_ms=1.0, obs=obs)
    wrong: list = []
    sent = [0] * 8

    def client(i):
        rng = np.random.default_rng(i)
        for _ in range(50):
            n = int(rng.integers(1, 4))
            sent[i] += n
            x = rng.standard_normal((n, 5)).astype(np.float32)
            got = np.asarray(server.query_batch(x, n, timeout=60.0))
            want = np.asarray(apply_fn(params, x))
            if got.shape != want.shape or not np.allclose(
                    got, want, rtol=1e-6, atol=1e-6):
                wrong.append((i, x, got))

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120.0)
        assert not t.is_alive()
    server.stop()
    assert not wrong
    assert server.stats["items"] == sum(sent)
    agg = obs.tracer.aggregates()
    assert agg["server.queue_wait"]["count"] == 8 * 50
    assert agg.get("server.ahead", {"count": 0})["count"] <= agg[
        "server.batch"]["count"]
    obs.close()
    # batch k's callers are released before batch k+1's: the scatters,
    # in time, carry rising batch numbers
    scatters = sorted((e for e in _events(tmp_path)
                       if e["name"] == "server.scatter"),
                      key=lambda e: e["ts"])
    seqs = [e["args"]["batch"] for e in scatters]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)


def test_lone_query_is_answered_without_waiting_for_a_second_request(
        tmp_path):
    """With nothing waiting the batch in flight is fetched at once: a
    lone actor pays ONE fill deadline, as ever, not a second wait on
    the queue after the dispatch. Held by the trace, not by how long
    the reply took on a loaded machine: batch 2's period holds one
    `server.collect`, the one that waited out the deadline, and its
    fetch starts inside that period with no collect after the
    dispatch."""
    obs = _traced_obs(tmp_path)
    server = BatchedInferenceServer(lambda p, x: x + p, np.float32(1.0),
                                    max_batch=8, deadline_ms=500.0,
                                    obs=obs)
    try:
        x = np.zeros(3, np.float32)
        server.query(x)                      # compiles bucket 1
        t0 = time.monotonic()
        got = server.query(x)
        took = time.monotonic() - t0
        np.testing.assert_allclose(np.asarray(got), 1.0)
        assert took >= 0.5, took
    finally:
        server.stop()   # joins the serve thread: its counters are final
        obs.close()
    assert server.stats["batches"] == 2
    ev = _events(tmp_path)
    assert not [e for e in ev if e["name"] == "server.ahead"]

    def end(e):
        return e["ts"] + e["dur"]

    def one(name, **args):
        (e,) = [e for e in ev if e["name"] == name
                and all(e["args"][k] == v for k, v in args.items())]
        return e

    period = one("server.period", behind=2)
    assert period["args"]["batch"] == 2   # dispatch and reply, one period
    dispatch, fetch = one("server.dispatch", batch=2), one("server.fetch",
                                                           batch=2)
    inside = [e for e in ev if e["name"] == "server.collect"
              and period["ts"] <= e["ts"] < end(period)]
    assert [e["args"]["batch"] for e in inside] == [2]
    assert inside[0]["dur"] >= 500e3      # the one deadline, in us
    assert end(inside[0]) <= dispatch["ts"] + 1
    assert end(dispatch) <= fetch["ts"] + 1
    assert period["ts"] <= fetch["ts"] and end(fetch) <= end(period) + 1


def test_at_most_one_batch_ahead():
    """Dispatched-and-unanswered batches, counted at every dispatch:
    two at most (the one in flight and the one ahead), and two are
    reached."""
    gate = _Gated()
    obs = _CountingObs()
    server = BatchedInferenceServer(gate, np.float32(3.0), max_batch=2,
                                    deadline_ms=1.0, obs=obs)
    real = server._apply
    dispatched = []

    def counting(params, stacked):
        dispatched.append(len(dispatched) + 1 - len(obs.served))
        return real(params, stacked)

    server._apply = counting
    out: dict = {}
    try:
        first = _ask(server, _rows(1.0, 2), 2, out, "first")
        behind = _enqueue_behind(
            server, gate,
            [(_rows(float(k), 2), 2, k) for k in range(2, 8)], out)
        _joined([first, *behind])
        # then free-running traffic from more threads than batch slots
        more = [_ask(server, _rows(float(k), 2), 2, out, k)
                for k in range(8, 40)]
        _joined(more)
    finally:
        server.stop()
    np.testing.assert_allclose(out["first"], 3.0)
    for k in range(2, 40):
        np.testing.assert_allclose(out[k], 3.0 * k)
    assert len(obs.served) == len(dispatched) == 39
    assert max(dispatched) == 2
    # the six queued behind batch 1 each went ahead of a predecessor
    assert dispatched[:7] == [1, 2, 2, 2, 2, 2, 2]


def _raises_in_trace(p, x):
    if x.shape[0] == 4:
        raise ValueError("bucket 4 does not trace")
    return x * p


def _raises_on_device(p, x):
    def check(a):
        if a[0, 0] < 0:
            raise ValueError("negative row on the device")
        return a

    return jax.pure_callback(
        check, jax.ShapeDtypeStruct(x.shape, x.dtype), x) * p


@pytest.mark.parametrize("where", ["dispatch", "device"])
def test_an_error_reaches_its_own_batch_and_no_other(where):
    """Three batches, the middle one bad, with its neighbours in
    flight around it. `dispatch`: the bad batch fails while it is
    stacked and enqueued AHEAD of batch 1's fetch. `device`: it fails
    in execution, so at its fetch (or, where the backend runs it
    inline, at its dispatch), with batch 3 ahead of it."""
    if where == "dispatch":
        gate, bad = _Gated(_raises_in_trace), _rows(2.0, 4)
    else:
        gate, bad = _Gated(_raises_on_device), _rows(-2.0, 4)
    server = BatchedInferenceServer(gate, np.float32(2.0), max_batch=4,
                                    deadline_ms=1.0)
    out: dict = {}
    try:
        first = _ask(server, _rows(1.0, 2), 2, out, "first")
        # bad fills a batch alone (4 of 4); last is held for the next
        behind = _enqueue_behind(
            server, gate, [(bad, 4, "bad"), (_rows(3.0, 2), 2, "last")],
            out)
        _joined([first, *behind])
        np.testing.assert_allclose(out["first"], 2.0)
        assert isinstance(out["bad"], Exception), out["bad"]
        assert ("bucket 4" in str(out["bad"])
                or "negative row" in str(out["bad"]))
        np.testing.assert_allclose(out["last"], 6.0)
        assert server.stats["batches"] == 2
        # and the server is whole: the next query is served
        np.testing.assert_allclose(
            np.asarray(server.query_batch(_rows(5.0, 2), 2)), 10.0)
    finally:
        server.stop()


def test_each_batch_keeps_the_version_it_was_dispatched_with():
    gate = _Gated()
    obs = _CountingObs()
    server = BatchedInferenceServer(gate, np.float32(2.0), max_batch=2,
                                    deadline_ms=1.0, obs=obs)
    out: dict = {}
    try:
        first = _ask(server, _rows(1.0, 2), 2, out, "first")
        assert gate.entered.wait(30.0)
        # batch 1 has read its params; a publish lands before batch 2's
        # dispatch, which happens before batch 1's fetch
        server.update_params(np.float32(10.0), version=7)
        behind = _enqueue_behind(server, gate,
                                 [(_rows(1.0, 2), 2, "second")], out)
        _joined([first, *behind])
    finally:
        server.stop()
    np.testing.assert_allclose(out["first"], 2.0)
    np.testing.assert_allclose(out["second"], 10.0)
    assert obs.served == [(2, 0), (2, 7)]


def test_stop_with_a_batch_in_flight_leaves_no_waiter():
    gate = _Gated()
    server = BatchedInferenceServer(gate, np.float32(2.0), max_batch=2,
                                    deadline_ms=1.0)
    out: dict = {}
    first = _ask(server, _rows(1.0, 2), 2, out, "first")
    assert gate.entered.wait(30.0)
    stopper = threading.Thread(target=server.stop, daemon=True)
    stopper.start()
    _wait(server._stop.is_set, "stop() did not set the flag")
    gate.release.set()      # the loop finds _stop set, a batch in flight
    _joined([first, stopper])
    np.testing.assert_allclose(out["first"], 2.0)
    assert not server._thread.is_alive()
    assert server.stats["batches"] == 1


def test_mesh_path_serves_through_the_pipeline(tmp_path):
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    gate = _Gated()
    obs = _traced_obs(tmp_path)
    server = BatchedInferenceServer(gate, np.float32(2.0), max_batch=8,
                                    deadline_ms=1.0, mesh=mesh, obs=obs)
    out: dict = {}
    try:
        first = _ask(server, _rows(1.0, 8), 8, out, "first")
        behind = _enqueue_behind(
            server, gate, [(_rows(2.0, 3), 3, "a"), (_rows(3.0, 5), 5, "b")],
            out)
        _joined([first, *behind])
    finally:
        server.stop()
    np.testing.assert_allclose(out["first"], 2.0)
    np.testing.assert_allclose(out["a"], 4.0)
    np.testing.assert_allclose(out["b"], 6.0)
    assert out["a"].shape == (3, 3) and out["b"].shape == (5, 3)
    assert server.stats == {"batches": 2, "items": 16, "avg_batch": 8.0}
    assert obs.tracer.aggregates()["server.ahead"]["count"] == 1
    obs.close()


def test_held_back_oversize_request_still_serves():
    """Behind batch 1: a 3-item request, a 6-item one (over max_batch:
    parked, then alone in a bucket of its own) and a single that still
    fits beside the first."""
    gate = _Gated()
    obs = _CountingObs()
    server = BatchedInferenceServer(gate, np.float32(2.0), max_batch=4,
                                    deadline_ms=1.0, obs=obs)
    out: dict = {}
    try:
        first = _ask(server, _rows(1.0, 4), 4, out, "first")
        behind = _enqueue_behind(
            server, gate,
            [(_rows(2.0, 3), 3, "three"), (_rows(3.0, 6), 6, "six"),
             (_rows(4.0, 1), 1, "one")], out)
        _joined([first, *behind])
    finally:
        server.stop()
    for key, n, v in (("first", 4, 2.0), ("three", 3, 4.0),
                      ("six", 6, 6.0), ("one", 1, 8.0)):
        assert out[key].shape == (n, 3), key
        np.testing.assert_allclose(out[key], v)
    assert [items for items, _ in obs.served] == [4, 4, 6]


def test_overlapping_batches_hold_their_own_children_and_ahead_counts_one(
        tmp_path):
    gate = _Gated()
    obs = _traced_obs(tmp_path)
    server = BatchedInferenceServer(gate, np.float32(2.0), max_batch=2,
                                    deadline_ms=1.0, obs=obs)
    out: dict = {}
    try:
        first = _ask(server, _rows(1.0, 2), 2, out, "first")
        behind = _enqueue_behind(server, gate,
                                 [(_rows(2.0, 2), 2, "second")], out)
        _joined([first, *behind])
    finally:
        server.stop()
    agg = obs.tracer.aggregates()
    assert agg["server.batch"]["count"] == 2
    assert agg["server.ahead"]["count"] == 1
    for name in CHILDREN:
        assert agg[name]["count"] == 2, name
    obs.close()
    ev = _events(tmp_path)

    def one(name, **args):
        found = [e for e in ev if e["name"] == name and all(
            e["args"].get(k) == v for k, v in args.items())]
        assert len(found) == 1, (name, args)
        return found[0]

    def end(e):
        return e["ts"] + e["dur"]

    b1, b2 = one("server.batch", seq=1), one("server.batch", seq=2)
    assert b1["ts"] < b2["ts"] < end(b1) < end(b2)        # they overlap
    for b in (b1, b2):
        for name in CHILDREN:
            child = one(name, batch=b["args"]["seq"])
            assert b["ts"] <= child["ts"] and end(child) <= end(b) + 1
    # the serve thread's order: 2 is stacked and dispatched, THEN 1 is
    # fetched and scattered, then 2
    order = [one("server.stack", batch=2), one("server.dispatch", batch=2),
             one("server.fetch", batch=1), one("server.scatter", batch=1),
             one("server.fetch", batch=2), one("server.scatter", batch=2)]
    for a, b in zip(order, order[1:]):
        assert end(a) <= b["ts"] + 1
    ahead = one("server.ahead")
    assert ahead["args"] == {"batch": 2, "behind": 1}
    # from 2's dispatch to the start of 1's fetch
    assert end(order[0]) <= ahead["ts"] + 1 <= order[1]["ts"] + 2
    assert end(order[1]) <= end(ahead) + 1 <= order[2]["ts"] + 2


def test_loopback_queue_holds_256_messages_then_drops_the_oldest():
    """The fleet behind the pipelined server ships half again as many
    messages a second; the loopback queue's depth in time went with it
    (PERF.md section 6, PR 40). Beyond it: the oldest goes, counted."""
    from ape_x_dqn_tpu.comm.transport import LoopbackTransport

    t = LoopbackTransport()
    for i in range(256):
        t.send_experience({"serial": i})
    assert (t.pending, t.dropped) == (256, 0)
    for i in range(256, 260):
        t.send_experience({"serial": i})
    assert (t.pending, t.dropped) == (256, 4)
    assert t.recv_experience(timeout=0.0)["serial"] == 4
    t.close()
