"""The inference server's slot path (parallel/inference_server.py, "The
slot path"; parallel/slot_pool.py) around the tiny MiniCPM-SALA net on
the CPU: a vector actor's two episodes through the slot server answer
what the family's stateless token window answers; two sessions in one
batch are each what they are alone, and `fresh` resets a slot; a slot
asked twice in one collect is served in two batches; a session that
does not fit fails its query by name and the rest of the batch is
served; a dispatch that fails leaves the ledger with the device and,
where it took the donated state, a zeroed state and its sessions lost
by name; warm-up compiles both bucket kinds and nothing compiles after
it; and a server WITHOUT slots is the parent's: its methods, its
jitted program and its spans."""

import json
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.configs import get_config
from ape_x_dqn_tpu.models import build_network
from ape_x_dqn_tpu.parallel.inference_server import (
    BatchedInferenceServer, _Request)
from ape_x_dqn_tpu.parallel.slot_pool import (
    SlotOverflow, SlotPool, SlotPoolFull, SlotStateLost)
from ape_x_dqn_tpu.runtime import family as fam


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("minicpm_sala_tiny_q")
    net = build_network(cfg.network, None)
    return cfg, net, net.init(jax.random.PRNGKey(0))


def slot_server(tiny, obs=None, **inference):
    cfg, net, params = tiny
    import dataclasses

    cfg = cfg.replace(inference=dataclasses.replace(cfg.inference,
                                                    **inference))
    return BatchedInferenceServer(
        fam.server_apply_fn("decoder_q", net, cfg), params,
        max_batch=cfg.inference.max_batch, deadline_ms=1.0, obs=obs,
        **fam.server_slots(cfg, net))


def rows(tokens, slots, fresh, **more):
    return {"obs": np.asarray(tokens, np.int32),
            "slot": np.asarray(slots, np.int32),
            "fresh": np.asarray(fresh, np.int32), **more}


def test_slot_path_is_the_stateless_window_over_two_episodes(tiny):
    """RecurrentActor, two envs, two episodes each and more: every
    Q the slot server answers is what `apply_window` answers over the
    same episode's tokens (episodes here are no longer than the
    window)."""
    from ape_x_dqn_tpu.comm.transport import LoopbackTransport
    from ape_x_dqn_tpu.runtime.actor import RecurrentActor

    cfg, net, params = tiny
    server = slot_server(tiny)
    window = jax.jit(fam.server_apply_fn(
        "decoder_q", types.SimpleNamespace(apply=net.apply)))
    assert window.__wrapped__.__name__ == "apply_window"
    held = {}           # slot -> the stateless protocol's (ctx, n)
    seen = {"queries": 0, "fresh": 0, "worst": 0.0}

    def query(inputs, n):
        out = server.query_batch(inputs, n)
        for j in range(n):
            slot = int(inputs["slot"][j])
            if inputs["fresh"][j]:
                held[slot] = fam.ACTOR_STATE["decoder_q"].zeros(cfg)
                seen["fresh"] += 1
            ctx, count = held[slot]["ctx"], held[slot]["n"]
            want = window(params, {"obs": inputs["obs"][j:j + 1],
                                   "ctx": ctx[None], "n": count[None]})
            held[slot] = {"ctx": np.asarray(want["ctx"][0]),
                          "n": np.asarray(want["n"][0])}
            seen["worst"] = max(seen["worst"], float(np.abs(
                np.asarray(want["q"][0]) - out["q"][j]).max()))
            seen["queries"] += 1
        assert out["fresh"].tolist() == [0] * n
        assert out["slot"].tolist() == inputs["slot"].tolist()
        return out

    try:
        actor = RecurrentActor(cfg, 0, query, LoopbackTransport())
        actor.run(max_frames=2 * 2 * 64 + 8)
    finally:
        server.stop()
    assert seen["fresh"] >= 4 and seen["queries"] >= 256
    assert seen["worst"] < 5e-6, seen
    assert set(held) == {0, 1}
    assert server.slot_counters["extend_tokens"] == seen["queries"]


def test_sessions_are_isolated_and_fresh_resets(tiny):
    _, _, _ = tiny
    rng = np.random.default_rng(0)
    a, b = rng.integers(0, 64, 40), rng.integers(0, 64, 40)

    def alone(tokens, slot):
        server = slot_server(tiny)
        try:
            return [server.query(rows(t, slot, i == 0))["q"]
                    for i, t in enumerate(tokens)]
        finally:
            server.stop()

    want_a, want_b = alone(a, 0), alone(b, 1)
    server = slot_server(tiny)
    try:
        for i in range(40):     # interleaved in ONE batch
            out = server.query_batch(
                rows([a[i], b[i]], [0, 1], [i == 0] * 2), 2)
            np.testing.assert_allclose(out["q"][0], want_a[i], atol=5e-6)
            np.testing.assert_allclose(out["q"][1], want_b[i], atol=5e-6)
        # slot 0 begins again with b's tokens while slot 1 runs on
        for i in range(8):
            out = server.query_batch(
                rows([b[i], a[i]], [0, 1], [i == 0, False]), 2)
            np.testing.assert_allclose(out["q"][0], want_b[i], atol=5e-6)
        assert "sel" not in out
        out = server.query_batch(
            rows([1, 2], [0, 1], [0, 0], want_sel=True), 2)
        assert out["sel"].shape == (2, 1, 2, 1, 6)
    finally:
        server.stop()


def test_a_slot_asked_twice_in_one_collect_is_served_in_two_batches(tiny):
    server = slot_server(tiny)
    try:
        server.query(rows(3, 0, 1))
        served = server.stats["batches"]
        server._stop.set()              # park the serve thread
        server._thread.join(timeout=5)
        first, second, other = (
            _Request(rows(5, 0, 0)), _Request(rows(6, 0, 0)),
            _Request(rows(7, 1, 1)))
        for r in (first, second, other):
            server._q.put(r)
        batch = server._collect(block=False)
        assert batch == [first, other]
        assert list(server._held) == [second]
        assert server._collect(block=False) == [second]
        # and a prefill chunk never shares a dispatch with decode rows
        chunk = _Request(rows(np.zeros(16), 2, 1,
                              n_valid=np.int32(16)))
        for r in (first, chunk, other):
            server._q.put(r)
        assert server._collect(block=False) == [first, other]
        assert server._collect(block=False) == [chunk]
        assert served == server.stats["batches"]
    finally:
        server.stop()


def test_a_reply_that_is_not_ready_is_waited_for_on_the_queue(tiny):
    """With a reply owed that the device has not finished, `_collect`
    takes what ARRIVES meanwhile (it rides the next dispatch); once the
    reply is ready it returns at once, as the stateless loop does. And
    a full batch pads no row."""
    server = slot_server(tiny, max_batch=6)
    try:
        server._stop.set()
        server._thread.join(timeout=5)
        server._stop.clear()

        class Owed:
            ready = False

            def is_ready(self):
                return self.ready

        server._owed = owed = Owed()
        late = _Request(rows(5, 0, 1))
        threading.Timer(0.05, server._q.put, args=(late,)).start()
        assert server._collect(block=False) == [late]
        owed.ready = True
        t0 = time.perf_counter()
        assert server._collect(block=False) == []
        assert time.perf_counter() - t0 < 0.05
        assert [server._slot_bucket(n, 1) for n in (1, 3, 5, 6, 7)] == [
            1, 4, 6, 6, 7]
        # and the warm-up runs the budget's own bucket, not the power
        # of two above it
        server.warmup(None)
        assert server.warm_buckets == [
            (1, 1), (1, 2), (1, 4), (1, 6), (16, 1), (16, 2)]
        assert [server._slot_bucket(n, 16) for n in (1, 2, 3)] == [1, 2, 3]
    finally:
        server._stop.set()


@pytest.fixture(scope="module")
def tiny_jamba():
    """The second net served from slots (models/jamba_q.py: a state that
    holds no blocks for most layers, and no selection to answer)."""
    cfg = get_config("jamba2_tiny_q")
    net = build_network(cfg.network, None)
    return cfg, net, net.init(jax.random.PRNGKey(0))


@pytest.mark.parametrize("budget, each, waiting", [
    (128, 8, 16), (128, 8, 20), (32, 8, 4), (8, 2, 5)])
def test_a_full_batch_closes_the_collect_and_pads_no_row(
        tiny_jamba, budget, each, waiting):
    """`budget` rows a step and requests of `each` rows (the wide cell's
    128 and 8): with `budget / each` requests waiting a collect takes
    exactly those and returns WITHOUT the fill deadline, the dispatch is
    the budget's own bucket, and what is left rides the next batch."""
    slots = waiting * each
    server = slot_server(tiny_jamba, max_batch=budget, slots=slots,
                         slot_max_len=16, slot_pool_tokens=slots * 128)
    server._deadline_s = 5.0        # a wait for it would show
    try:
        server._stop.set()              # park the serve thread
        server._thread.join(timeout=5)
        reqs = [_Request(rows(np.full(each, i), np.arange(
            i * each, (i + 1) * each), np.ones(each)), each)
            for i in range(waiting)]
        for r in reqs:
            server._q.put(r)
        full = budget // each
        t0 = time.perf_counter()
        batch = server._collect(block=True)
        assert time.perf_counter() - t0 < 1.0
        assert batch == reqs[:full]
        assert server._slot_bucket(budget, 1) == budget
        flight = server._dispatch(batch)
        assert (flight.n, flight.padded) == (budget, budget)
        server._reply(flight)
        assert [r.result["q"].shape for r in batch] == [(each, 64)] * full
        assert "sel" not in batch[0].result     # the net selects nothing
        rest = server._collect(block=False)
        assert rest == reqs[full:]
        if rest:
            flight = server._dispatch(rest)
            assert flight.n == (waiting - full) * each
            assert flight.n <= flight.padded <= budget
            server._reply(flight)
        assert server.slot_counters["extend_tokens"] == slots
        assert server.slot_counters["ssm_rows_updated"] == 4 * slots
        assert server.slot_ledger["slots_live"] == slots
    finally:
        server.stop()


def test_a_wide_fleet_is_answered_as_each_session_alone(tiny_jamba):
    """8 client threads x 4 sessions through a 16-row server, prefill
    chunks then decode steps, `want_sel` said by one of them: every
    session's last Q is what the same tokens give in a server of their
    own."""
    cfg, net, params = tiny_jamba
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, 64, (32, 24)).astype(np.int32)

    def drive(server, sessions, slot_of):
        k = len(sessions)
        slots = np.asarray([slot_of(i) for i in sessions], np.int32)
        out = server.query_batch(rows(
            tokens[sessions, :16], slots, np.ones(k),
            n_valid=np.full(k, 16, np.int32),
            **({"want_sel": True} if 0 in sessions else {})), k)
        for t in range(16, 24):
            out = server.query_batch(
                rows(tokens[sessions, t], slots, np.zeros(k)), k)
        return out["q"]

    server = slot_server(tiny_jamba, max_batch=16, slots=32,
                         slot_max_len=32, slot_pool_tokens=32 * 128)
    got = {}
    try:
        def client(i):
            mine = list(range(4 * i, 4 * i + 4))
            got[i] = drive(server, mine, lambda s: s)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert server.slot_counters["extend_tokens"] == 32 * 24
        assert np.asarray(net.slot_lengths(server.slot_state))[:32].tolist() \
            == [24] * 32
    finally:
        server.stop()
    alone = slot_server(tiny_jamba, max_batch=16, slots=32,
                        slot_max_len=32, slot_pool_tokens=32 * 128)
    try:
        for i in (0, 3, 7):
            want = drive(alone, list(range(4 * i, 4 * i + 4)),
                         lambda s: s % 4)
            np.testing.assert_allclose(got[i], want, atol=1e-5, rtol=0)
    finally:
        alone.stop()


def test_admission_beyond_the_pool_fails_by_name(tiny):
    # room for two sessions of 65 positions; three slots
    server = slot_server(tiny, slots=3, slot_pool_tokens=2 * 72)
    try:
        out = server.query_batch(rows([1, 2], [0, 1], [1, 1]), 2)
        assert out["q"].shape == (2, 64)
        results = {}

        def ask(name, inputs):
            try:
                results[name] = server.query(inputs)
            except Exception as e:
                results[name] = e

        threads = [threading.Thread(target=ask, args=("full", rows(3, 2, 1))),
                   threading.Thread(target=ask, args=("fine", rows(4, 0, 0)))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert isinstance(results["full"], SlotPoolFull)
        assert "no free range" in str(results["full"])
        assert results["fine"]["q"].shape == (64,)
        # a shorter session fits what a longer one could not
        assert server.query(rows(3, 2, 1, max_len=np.int32(0)))  # still 65
    except SlotPoolFull:
        pass
    finally:
        server.stop()
    assert server.slot_ledger == {"slots_live": 2, "blocks_held": 18,
                                  "pool_blocks": 18}
    # a session that sends more than it declared, and one never admitted
    small = SlotPool(2, 4, 8, 32)
    small.admit(0, 16)
    small.advance(0, 16)
    with pytest.raises(SlotOverflow, match="past the 16 it declared"):
        small.advance(0, 1)
    with pytest.raises(SlotOverflow, match="never admitted"):
        small.advance(1, 1)
    with pytest.raises(SlotOverflow, match="inference.slot_max_len"):
        small.admit(1, 33)
    assert small.admit(1, 8) == 2 and small.free(0) == 2
    assert small.admit(0, 9) == 0 and small.blocks_held == 3


@pytest.mark.parametrize("consumed", [False, True])
def test_a_dispatch_that_fails_leaves_the_ledger_with_the_device(
        tiny, consumed):
    """The program raises once. Its own requests get the error and the
    ledger goes back to what the device's lengths agree with: where the
    donated state survived (a trace or compile error) both sessions
    answer on exactly as if the failed step had never been sent; where
    the failure took the state with it, the state is zeroed, the
    sessions that were live fail by name, and one that begins again is
    served."""
    _, net, _ = tiny
    a = np.random.default_rng(1).integers(0, 64, 12)

    def serve(server, i):
        return server.query_batch(rows([a[i], a[i] + 1], [0, 1],
                                       [i == 0] * 2), 2)["q"]

    whole = slot_server(tiny)
    try:
        want = [serve(whole, i) for i in range(6)]
    finally:
        whole.stop()
    server = slot_server(tiny)
    try:
        got = [serve(server, i) for i in range(3)]
        real = server._apply

        def failing(params, state, stacked):
            server._apply = real        # once
            if consumed:
                for leaf in jax.tree.leaves(state):
                    leaf.delete()
            raise RuntimeError("planted")

        server._apply = failing
        with pytest.raises(RuntimeError, match="planted"):
            serve(server, 3)
        if consumed:
            for slot in (0, 1):
                with pytest.raises(SlotStateLost, match="begin again"):
                    server.query(rows(a[3], slot, 0))
            # a session that begins again is served, from a zeroed slot
            q = server.query_batch(rows([a[0], a[0] + 1], [0, 1],
                                        [1, 1]), 2)["q"]
            np.testing.assert_allclose(q, want[0], atol=5e-6)
            lengths = [1, 1]
        else:
            got += [serve(server, i) for i in range(3, 6)]
            np.testing.assert_allclose(got, want, atol=5e-6)
            lengths = [6, 6]
    finally:
        server.stop()
    assert np.asarray(net.slot_lengths(
        server.slot_state)).tolist()[:2] == lengths
    assert server.slot_ledger["slots_live"] == 2


def test_warmup_compiles_both_kinds_and_nothing_after(tiny):
    from ape_x_dqn_tpu.obs.profiling import CompileWatcher

    cfg, _, _ = tiny
    server = slot_server(tiny)
    try:
        server.warmup(fam.warmup_example("decoder_q", cfg,
                                         types.SimpleNamespace(
                                             obs_shape=(), obs_dtype=np.int32)))
        assert server.warm_buckets == [
            (1, 1), (1, 2), (1, 4), (1, 8), (16, 1), (16, 2)]
        watcher = CompileWatcher.install()
        before = watcher.snapshot()[0]
        server.query_batch(rows([1, 2, 3], [0, 1, 2], [1, 1, 1]), 3)
        out = server.query_batch(rows(
            np.ones((2, 16)), [0, 1], [1, 1],
            n_valid=np.asarray([16, 9], np.int32)), 2)
        assert out["q"].shape == (2, 64)
        assert watcher.snapshot()[0] == before
        assert server.slot_counters["extend_tokens"] == 3 + 25
        # the ragged row holds 9 positions
        server.stop()
        _, net, _ = tiny
        assert np.asarray(net.slot_lengths(
            server.slot_state)).tolist()[:2] == [16, 9]
    finally:
        server.stop()


def test_the_spans_carry_n_and_rows_and_the_gauges_read(tiny, tmp_path):
    from ape_x_dqn_tpu.configs import ObsConfig
    from ape_x_dqn_tpu.obs import build_obs
    from ape_x_dqn_tpu.utils.metrics import Metrics

    obs = build_obs(ObsConfig(enabled=True,
                              trace_path=str(tmp_path / "trace.json")),
                    Metrics())
    server = slot_server(tiny, obs=obs)
    try:
        server.query_batch(rows([1, 2, 3], [0, 1, 2], [1, 1, 1]), 3)
        server.query(rows(np.ones(16), 0, 1, n_valid=np.int32(16)))
    finally:
        server.stop()
    obs.tracer.close()
    with open(tmp_path / "trace.json") as fh:
        events = [e for e in json.load(fh)["traceEvents"]
                  if e["ph"] != "M"]
    args = {(e["name"], e["args"].get("n"), e["args"].get("rows"))
            for e in events if e["name"] in ("server.stack",
                                             "server.dispatch")}
    assert args == {("server.stack", 1, 3), ("server.dispatch", 1, 3),
                    ("server.stack", 16, 1), ("server.dispatch", 16, 1)}
    marks = [e for e in events if e["name"].startswith("server.slot_")]
    assert [m["name"] for m in marks] == [
        "server.slot_admit"] * 3 + ["server.slot_free", "server.slot_admit"]
    assert obs.registry.gauge("server.slots_live").value == 3
    assert obs.registry.gauge("server.slot_blocks_held").value == 27


def test_a_server_without_slots_is_the_parents():
    """The choice is made once at construction: no slot method is
    bound, the jitted program is `jax.jit(apply_fn)`'s, the spans are
    the four of PR 40 with `batch=` alone."""
    from ape_x_dqn_tpu.obs.core import NULL_OBS

    def apply_fn(p, x):
        return {"q": x @ p}

    params = jnp.ones((4, 3))

    class Recorder(type(NULL_OBS)):
        def __init__(self):
            self.spans = []

        def span(self, name, **args):
            self.spans.append((name, tuple(sorted(args))))
            return NULL_OBS.span(name)

    obs = Recorder()
    server = BatchedInferenceServer(apply_fn, params, max_batch=4, obs=obs)
    try:
        for name in ("_collect", "_dispatch", "_reply"):
            assert getattr(server, name).__func__ is getattr(
                BatchedInferenceServer, name), name
        assert server._slots is None and not hasattr(server, "slot_counters")
        x = np.ones((2, 4), np.float32)
        assert (server._apply.lower(params, x).as_text()
                == jax.jit(apply_fn).lower(params, x).as_text())
        out = server.query(np.ones(4, np.float32))
        np.testing.assert_allclose(out["q"], 4.0)
    finally:
        server.stop()
    assert set(obs.spans) == {
        ("server.stack", ("batch",)), ("server.dispatch", ("batch",)),
        ("server.fetch", ("batch",)), ("server.scatter", ("batch",))}
