"""The actor runtime's K-env loops (runtime/actor.py) and the
inference server's multi-item query path that serves them
(SURVEY.md §2.4 "inference batching parallelism", §7 hard part 3)."""

import threading

import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.configs import (
    ActorConfig, EnvConfig, InferenceConfig, LearnerConfig, NetworkConfig,
    ReplayConfig, get_config)
from ape_x_dqn_tpu.comm.transport import LoopbackTransport
from ape_x_dqn_tpu.parallel.inference_server import BatchedInferenceServer
from ape_x_dqn_tpu.envs.base import Env, EnvSpec
from ape_x_dqn_tpu.runtime.actor import (
    Actor, ContinuousActor, RecurrentActor, actor_epsilon)
from ape_x_dqn_tpu.runtime.driver import ApexDriver
from ape_x_dqn_tpu.runtime.family import actor_class


# -- server query_batch ----------------------------------------------------

def test_query_batch_slices_match_items():
    """Mixed single + multi-item requests scatter the right slices."""
    def apply_fn(params, obs):
        return obs * params

    server = BatchedInferenceServer(apply_fn, jnp.float32(2.0),
                                    max_batch=16, deadline_ms=5.0)
    try:
        results = {}

        def single(i):
            results[("s", i)] = server.query(
                np.full(3, float(i), np.float32))

        def batch(i, n):
            inp = np.stack([np.full(3, 100.0 * i + j, np.float32)
                            for j in range(n)])
            results[("b", i)] = server.query_batch(inp, n)

        threads = ([threading.Thread(target=single, args=(i,))
                    for i in range(4)]
                   + [threading.Thread(target=batch, args=(i, 5))
                      for i in range(3)])
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i in range(4):
            np.testing.assert_allclose(results[("s", i)],
                                       np.full(3, 2.0 * i), rtol=1e-6)
        for i in range(3):
            want = np.stack([np.full(3, 2.0 * (100.0 * i + j), np.float32)
                             for j in range(5)])
            np.testing.assert_allclose(results[("b", i)], want, rtol=1e-6)
        assert server.stats["items"] == 4 + 3 * 5
    finally:
        server.stop()


def test_query_batch_larger_than_max_batch():
    """A vector request may exceed max_batch; the bucket pads past it."""
    def apply_fn(params, obs):
        return obs + params

    server = BatchedInferenceServer(apply_fn, jnp.float32(1.0),
                                    max_batch=4, deadline_ms=1.0)
    try:
        inp = np.arange(10, dtype=np.float32).reshape(10, 1)
        out = server.query_batch(inp, 10)
        np.testing.assert_allclose(out, inp + 1.0, rtol=1e-6)
    finally:
        server.stop()


# -- vector actor ----------------------------------------------------------

def _vec_cfg(num_actors=1, envs_per_actor=4):
    return get_config("cartpole_smoke").replace(
        actors=ActorConfig(num_actors=num_actors, base_eps=0.6,
                           envs_per_actor=envs_per_actor, ingest_batch=16),
        replay=ReplayConfig(kind="prioritized", capacity=2048, min_fill=64),
        learner=LearnerConfig(batch_size=32, n_step=3,
                              target_sync_every=100, publish_every=20),
        inference=InferenceConfig(max_batch=16, deadline_ms=1.0),
    )


def test_vector_actor_ships_prioritized_batches():
    cfg = _vec_cfg(envs_per_actor=4)
    transport = LoopbackTransport()
    calls = {"n": []}

    def query_fn(obs, n):
        calls["n"].append(n)
        assert obs.shape == (n, 4)
        return np.tile(np.array([0.1, 0.2], np.float32), (n, 1))

    actor = Actor(cfg, 0, query_fn, transport)
    frames = actor.run(max_frames=200)
    assert frames >= 200 and frames % 4 == 0
    # one K-item query per vector step (plus rare truncation queries)
    assert calls["n"].count(4) >= frames // 4
    batches, total = [], 0
    while True:
        b = transport.recv_experience(timeout=0.01)
        if b is None:
            break
        batches.append(b)
        total += len(b["priorities"])
    assert batches, "vector actor shipped nothing"
    b0 = batches[0]
    assert b0["obs"].shape[1:] == (4,)
    assert b0["priorities"].dtype == np.float32
    assert (b0["priorities"] >= 0).all()
    assert np.isfinite(b0["priorities"]).all()
    # n-step=3 over >=200 frames across 4 envs: most steps emit
    assert total > 120
    # frame accounting reconciles: shipped frames == stepped frames
    assert sum(b["frames"] for b in batches) == frames


def test_vector_actor_eps_spans_global_slots():
    """Actor i's env j sits at global eps slot i*K+j of N*K."""
    cfg = _vec_cfg(num_actors=2, envs_per_actor=3)

    def query_fn(obs, n):
        return np.zeros((n, 2), np.float32)

    a1 = Actor(cfg, 1, query_fn, LoopbackTransport())
    want = [actor_epsilon(1 * 3 + j, 6, 0.6, cfg.actors.eps_alpha)
            for j in range(3)]
    got = [c.eps for c in a1.cores]
    np.testing.assert_allclose(got, want)


def test_vector_actor_frame_ring_segments():
    """Frame-ring mode: per-env segment builders ship valid segments
    through the vector loop (synthetic-atari pixels)."""
    cfg = get_config("pong").replace(
        env=EnvConfig(id="catch", kind="synthetic_atari"),
        actors=ActorConfig(num_actors=1, envs_per_actor=3,
                           ingest_batch=16),
        replay=ReplayConfig(kind="prioritized", capacity=4096,
                            min_fill=64, storage="frame_ring",
                            seg_transitions=8),
        learner=LearnerConfig(batch_size=16, n_step=3),
    )
    transport = LoopbackTransport()

    def query_fn(obs, n):
        assert obs.shape[0] == n and obs.shape[1:] == (84, 84, 4)
        return np.zeros((n, 6), np.float32)

    actor = Actor(cfg, 0, query_fn, transport)
    frames = actor.run(max_frames=300)
    assert frames >= 300
    segs = []
    while True:
        b = transport.recv_experience(timeout=0.01)
        if b is None:
            break
        segs.append(b)
    assert segs, "no segments shipped"
    s0 = segs[0]
    f = cfg.replay.seg_transitions + cfg.learner.n_step + 4 - 1
    assert s0["seg_frames"].shape == (1, f, 84, 84)
    assert s0["action"].shape == (1, 8)
    assert (s0["priorities"] >= 0).all()
    assert sum(s["frames"] for s in segs) <= frames


def _r2d2_vec_cfg(num_actors=1, envs_per_actor=3, seq=8, overlap=4):
    from ape_x_dqn_tpu.configs import EnvConfig, ParallelConfig
    return get_config("r2d2").replace(
        env=EnvConfig(id="CartPolePO", kind="cartpole_po"),
        network=NetworkConfig(kind="lstm_q", lstm_size=32, torso_dense=64,
                              dueling=True, compute_dtype="float32"),
        replay=ReplayConfig(kind="sequence", capacity=512, seq_length=seq,
                            seq_overlap=overlap, burn_in=4,
                            min_fill=32, priority_eta=0.9, storage="flat"),
        learner=LearnerConfig(batch_size=16, n_step=3, value_rescale=True,
                              target_sync_every=100, lr=1e-3,
                              publish_every=25, train_chunk=4),
        actors=ActorConfig(num_actors=num_actors, base_eps=0.4,
                           envs_per_actor=envs_per_actor, ingest_batch=64),
        inference=InferenceConfig(max_batch=16, deadline_ms=1.0),
        parallel=ParallelConfig(dp=1, tp=1),
        eval_every_steps=0, eval_episodes=0,
    )


def test_recurrent_vector_actor_ships_sequences():
    cfg = _r2d2_vec_cfg(envs_per_actor=3)
    transport = LoopbackTransport()
    lstm = cfg.network.lstm_size

    def query_fn(inp, n):
        assert inp["obs"].shape[0] == n and inp["c"].shape == (n, lstm)
        return {"q": np.tile(np.array([0.1, 0.2], np.float32), (n, 1)),
                "c": np.asarray(inp["c"]) + 1.0,
                "h": np.asarray(inp["h"]) + 1.0}

    actor = RecurrentActor(cfg, 0, query_fn, transport)
    frames = actor.run(max_frames=120)
    assert frames >= 120 and frames % 3 == 0
    batches, total = [], 0
    while True:
        b = transport.recv_experience(timeout=0.01)
        if b is None:
            break
        batches.append(b)
        total += len(b["priorities"])
    assert batches, "vector recurrent actor shipped nothing"
    b0 = batches[0]
    seq = cfg.replay.seq_length
    assert b0["obs"].shape[1:] == (seq, 2)
    assert b0["actions"].shape[1:] == (seq,)
    assert b0["init_c"].shape[1:] == (lstm,)
    assert (b0["priorities"] > 0).all()
    assert (b0["mask"].sum(axis=1) >= 1).all()
    assert sum(b["frames"] for b in batches) == frames
    # init states advance with the fake recurrence except at episode
    # starts (zeros)
    assert any(np.any(b["init_c"] != 0) for b in batches)


def test_r2d2_driver_vector_end_to_end():
    """Recurrent vector actors through the real driver: batched
    stateful inference -> sequence ingest -> sequence learner."""
    cfg = _r2d2_vec_cfg(num_actors=1, envs_per_actor=3)
    driver = ApexDriver(cfg)
    assert driver.family == "r2d2"
    out = driver.run(total_env_frames=2000, max_grad_steps=40,
                     wall_clock_limit_s=240)
    assert out["actor_errors"] == [], out["actor_errors"]
    assert out["loop_errors"] == [], out["loop_errors"]
    assert out["grad_steps"] >= 40, out
    assert out["frames"] >= 100, out
    assert out["server"]["avg_batch"] > 1.5, out["server"]


def test_apex_driver_vector_end_to_end():
    """Full wiring with vector actors: one thread, 4 envs, batched
    queries through the real inference server into the learner."""
    cfg = _vec_cfg(num_actors=1, envs_per_actor=4).replace(
        eval_every_steps=0, eval_episodes=0)  # eval's single-item
    # queries would dilute the avg_batch assertion below
    driver = ApexDriver(cfg)
    out = driver.run(total_env_frames=1600, max_grad_steps=50,
                     wall_clock_limit_s=120)
    assert out["actor_errors"] == [], out["actor_errors"]
    assert out["loop_errors"] == [], out["loop_errors"]
    assert out["frames"] >= 64, out
    assert out["grad_steps"] >= 50, out
    assert out["episodes"] > 0
    # the server saw multi-item requests: avg batch well above 1
    assert out["server"]["avg_batch"] > 2.0, out["server"]


# -- one env an actor: K = 1 of the same loops -----------------------------

class _Scripted(Env):
    """obs = [steps into the episode], reward = that count after the
    step; episode 0 is TRUNCATED at its 4th step, episode 1 ends in a
    TERMINAL at its 3rd, the later ones run on."""

    def __init__(self, spec):
        self.spec = spec
        self.episode, self.t = -1, 0

    def reset(self):
        self.episode += 1
        self.t = 0
        return np.zeros(self.spec.obs_shape, np.float32)

    def step(self, action):
        self.t += 1
        obs = np.full(self.spec.obs_shape, self.t, np.float32)
        ends = {0: 4, 1: 3}.get(self.episode)
        done = self.t == ends
        info = {"terminal": done and self.episode == 1}
        if done:
            info["episode_return"] = float(sum(range(1, self.t + 1)))
        return obs, float(self.t), done, info


class _Capture:
    def __init__(self):
        self.batches = []

    def send_experience(self, batch):
        self.batches.append(batch)


_DISCRETE = EnvSpec((1,), np.dtype(np.float32), True, num_actions=2)
_BOX = EnvSpec((1,), np.dtype(np.float32), False, action_dim=1)


def _half_q(obs, n):
    # Q(o) = [o / 2, -1]: the greedy action is 0 and V(o) = o / 2
    o = np.asarray(obs, np.float32).reshape(n)
    return np.stack([o / 2, -np.ones(n, np.float32)], axis=1)


def _half_mu_q(obs, n):
    o = np.asarray(obs, np.float32).reshape(n)
    return {"a": np.zeros((n, 1), np.float32), "q": o / 2}


def _half_lstm_q(inp, n):
    return {"q": _half_q(inp["obs"], n), "c": np.asarray(inp["c"]) + 1.0,
            "h": np.asarray(inp["h"]) - 1.0}


# gamma = 1/2, n_step = 2, greedy, over the 7 frames of _Scripted's
# first two episodes. Flat families: a transition from step t is
# (o_t, r_t + r_{t+1} / 2, o_{t+2}, 1/4), shortened at an episode's end
# (truncation: bootstraps from the 4th step's own observation, o = 4;
# terminal: discount 0); its priority is |R + discount * V(next) - V(o_t)|.
_FLAT_WANT = {
    "obs": [0, 1, 2, 3, 0, 1, 2],
    "reward": [1 + 2 / 2, 2 + 3 / 2, 3 + 4 / 2, 4, 1 + 2 / 2, 2 + 3 / 2, 3],
    "next_obs": [2, 3, 4, 4, 2, 3, 3],
    "discount": [.25, .25, .25, .5, .25, 0, 0],
    "priorities": [2 + .25 * 1 - 0, 3.5 + .25 * 1.5 - .5, 5 + .25 * 2 - 1,
                   4 + .5 * 2 - 1.5, 2 + .25 * 1 - 0, 3.5 - .5, 3 - 1],
}
# Sequences of 4: one an episode; a step's 1-step TD is
# r_t + V(o_{t+1}) / 2 - V(o_t), the priority 0.9 max + 0.1 mean of them
_TD = [[1 + .25 - 0, 2 + .5 - .5, 3 + .75 - 1, 4 + 1 - 1.5],
       [1 + .25 - 0, 2 + .5 - .5, 3 - 1]]
_SEQ_WANT = {
    "obs": [[0, 1, 2, 3], [0, 1, 2, 0]],
    "actions": [[0, 0, 0, 0], [0, 0, 0, 0]],
    "rewards": [[1, 2, 3, 4], [1, 2, 3, 0]],
    "terminals": [[0, 0, 0, 0], [0, 0, 1, 0]],
    "mask": [[1, 1, 1, 1], [1, 1, 1, 0]],
    "init_c": [[0, 0], [0, 0]], "init_h": [[0, 0], [0, 0]],
    "priorities": [.9 * max(td) + .1 * sum(td) / len(td) for td in _TD],
}


def _scripted_cfg(family):
    learner = LearnerConfig(batch_size=4, n_step=2, gamma=0.5)
    actors = ActorConfig(num_actors=1, envs_per_actor=1, base_eps=0.0,
                         noise_sigma=0.0, ingest_batch=64)
    if family == "r2d2":
        return _r2d2_vec_cfg(seq=4, overlap=0).replace(
            network=NetworkConfig(kind="lstm_q", lstm_size=2),
            learner=learner, actors=actors)
    return get_config({"dqn": "cartpole_smoke", "dpg": "apex_dpg"}[family]
                      ).replace(learner=learner, actors=actors)


@pytest.mark.parametrize("family, spec, query, want", [
    ("dqn", _DISCRETE, _half_q, _FLAT_WANT),
    ("dpg", _BOX, _half_mu_q, _FLAT_WANT),
    ("r2d2", _DISCRETE, _half_lstm_q, _SEQ_WANT)])
def test_one_env_stream_is_the_arithmetic_by_hand(monkeypatch, family,
                                                  spec, query, want):
    """K = 1 of each family's class through a truncation and a terminal:
    what it ships is the n-step (the 1-step, for sequences) arithmetic
    worked out above, and the truncation costs one query more."""
    monkeypatch.setattr("ape_x_dqn_tpu.runtime.actor.make_env",
                        lambda cfg, seed=0, actor_index=0: _Scripted(spec))
    asked, episodes = [], []

    def counted(inputs, n):
        asked.append(n)
        return query(inputs, n)

    transport = _Capture()
    actor = actor_class(family)(
        _scripted_cfg(family), 0, counted, transport,
        episode_callback=lambda i, info: episodes.append(
            info["episode_return"]))
    assert actor.run(max_frames=7) == 7
    assert asked == [1] * 8 and episodes == [10.0, 6.0]
    (batch,) = transport.batches
    assert batch["frames"] == 7 and batch["actor"] == 0
    for key, value in want.items():
        got = np.asarray(batch[key])
        np.testing.assert_allclose(
            got.reshape(np.shape(value)), value, rtol=1e-6, err_msg=key)


@pytest.mark.parametrize("family, cls", [
    ("dqn", Actor), ("dpg", ContinuousActor), ("r2d2", RecurrentActor),
    ("decoder_q", RecurrentActor)])
def test_one_actor_class_a_family(family, cls):
    import inspect

    assert actor_class(family) is cls
    assert list(inspect.signature(actor_class).parameters) == ["family"]


def test_spans_and_ship_marks_of_the_k_env_loops():
    """`actor.env_step` beside `actor.inference`, one a vector step, and
    an `actor.ship` mark a shipment, from both loops."""
    class _Obs:
        def __init__(self):
            self.spans, self.marks, self.beats = [], [], 0

        def beat(self, name):
            self.beats += 1

        def span(self, name, **args):
            import contextlib
            self.spans.append((name, args))
            return contextlib.nullcontext()

        def mark(self, name, **args):
            self.marks.append((name, args))

    for cfg, cls, query, unit in (
            (_vec_cfg(envs_per_actor=2), Actor,
             lambda obs, n: np.zeros((n, 2), np.float32), "rows"),
            (_r2d2_vec_cfg(envs_per_actor=2), RecurrentActor,
             lambda inp, n: {"q": np.zeros((n, 2), np.float32),
                             "c": inp["c"], "h": inp["h"]}, "sequences")):
        obs, transport = _Obs(), _Capture()
        frames = cls(cfg, 0, query, transport, obs=obs).run(max_frames=200)
        steps = frames // 2
        assert obs.beats == steps
        for name in ("actor.inference", "actor.env_step"):
            assert obs.spans.count((name, {"k": 2})) == steps, name
        assert [m[0] for m in obs.marks] == (["actor.ship"]
                                             * len(transport.batches))
        assert ([m[1][unit] for m in obs.marks]
                == [len(b["priorities"]) for b in transport.batches])


@pytest.mark.parametrize("preset, overrides", [
    ("cartpole_smoke", dict(
        replay=ReplayConfig(kind="prioritized", capacity=2048, min_fill=64),
        learner=LearnerConfig(batch_size=32, n_step=3))),
    ("minicpm_sala_tiny_q", {})])
def test_a_preset_of_one_env_an_actor_ships_through_the_driver(
        preset, overrides):
    """`envs_per_actor=1` is a size of the same class: the driver builds
    it over `query_batch` (the slot row for a net the server keeps in
    slots) and its experience reaches the learner."""
    from ape_x_dqn_tpu.runtime.family import keeps_slots

    cfg = get_config(preset).replace(
        eval_every_steps=0, eval_episodes=0, **overrides)
    cfg = cfg.replace(actors=ActorConfig(
        num_actors=2, envs_per_actor=1, ingest_batch=16))
    assert keeps_slots(cfg) == (preset == "minicpm_sala_tiny_q")
    driver = ApexDriver(cfg)
    out = driver.run(total_env_frames=1200, max_grad_steps=4,
                     wall_clock_limit_s=240)
    made = list(driver._slot_actor_obj.values())
    assert out["actor_errors"] == [] and out["loop_errors"] == [], out
    assert out["grad_steps"] >= 4 and out["frames"] >= 64, out
    assert len(made) == 2 and all(
        type(a) is actor_class(driver.family) and a.K == 1
        and a.query == driver.server.query_batch for a in made)
    if keeps_slots(cfg):
        assert set(made[0].cores[0].state) == {"slot", "fresh"}
        assert driver.server.slot_counters["extend_tokens"] >= out["frames"]
