"""R2D2 runtime: recurrent actor, sequence learner, and the full driver
wiring over stored-state sequence replay (SURVEY.md §2.1 config 4)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.configs import (
    ActorConfig, EnvConfig, InferenceConfig, LearnerConfig, NetworkConfig,
    ParallelConfig, ReplayConfig, get_config)
from ape_x_dqn_tpu.comm.transport import LoopbackTransport
from ape_x_dqn_tpu.envs import make_env
from ape_x_dqn_tpu.models import ApeXLSTMQNet
from ape_x_dqn_tpu.replay.prioritized import PrioritizedReplay
from ape_x_dqn_tpu.replay.sequence import (
    SequenceBuilder, sequence_item_spec, split_priorities)
from ape_x_dqn_tpu.runtime.actor import RecurrentActor
from ape_x_dqn_tpu.runtime.driver import ApexDriver
from ape_x_dqn_tpu.runtime.family import r2d2_family
from ape_x_dqn_tpu.runtime.learner import SingleChipLearner


def _r2d2_cfg(num_actors=2, lstm=32, seq=16, overlap=8, burn_in=4):
    return get_config("r2d2").replace(
        env=EnvConfig(id="CartPolePO", kind="cartpole_po"),
        network=NetworkConfig(kind="lstm_q", lstm_size=lstm, torso_dense=64,
                              dueling=True, compute_dtype="float32"),
        replay=ReplayConfig(kind="sequence", capacity=512, seq_length=seq,
                            seq_overlap=overlap, burn_in=burn_in,
                            min_fill=32, priority_eta=0.9),
        learner=LearnerConfig(batch_size=16, n_step=3, value_rescale=True,
                              target_sync_every=100, lr=1e-3,
                              publish_every=25, train_chunk=4),
        actors=ActorConfig(num_actors=num_actors, base_eps=0.4,
                           ingest_batch=64),
        inference=InferenceConfig(max_batch=8, deadline_ms=1.0),
        parallel=ParallelConfig(dp=1, tp=1),
        eval_every_steps=0,
    )


def test_masked_cartpole_hides_velocities():
    env = make_env(EnvConfig(kind="cartpole_po"), seed=0)
    obs = env.reset()
    assert obs.shape == (2,)
    obs2, r, done, info = env.step(1)
    assert obs2.shape == (2,) and r == 1.0


def test_sequence_builder_actor_side_priority():
    sb = SequenceBuilder(seq_len=4, overlap=0, lstm_size=2,
                         priority_eta=0.9)
    pre = (np.zeros(2), np.zeros(2))
    out = []
    for t, td in enumerate([1.0, 2.0, 3.0, 4.0]):
        out += sb.append(np.array([t]), t, 0.0, False, pre, td=td)
    assert len(out) == 1
    # eta-mix: 0.9*max + 0.1*mean = 0.9*4 + 0.1*2.5
    np.testing.assert_allclose(out[0]["priority"], 0.9 * 4 + 0.1 * 2.5)
    items, pris = split_priorities(out)
    assert "priority" not in items[0]
    np.testing.assert_allclose(pris, [out[0]["priority"]])


def test_recurrent_actor_ships_sequences():
    cfg = _r2d2_cfg(num_actors=1, seq=8, overlap=4)
    transport = LoopbackTransport()
    lstm = cfg.network.lstm_size

    def query_fn(inp, n):
        # fake recurrent net: state accumulates, q fixed
        return {"q": np.tile(np.array([0.1, 0.2], np.float32), (n, 1)),
                "c": np.asarray(inp["c"]) + 1.0,
                "h": np.asarray(inp["h"]) + 1.0}

    actor = RecurrentActor(cfg, 0, query_fn, transport)
    frames = actor.run(max_frames=100)
    assert frames == 100
    batches, total = [], 0
    while True:
        b = transport.recv_experience(timeout=0.01)
        if b is None:
            break
        batches.append(b)
        total += len(b["priorities"])
    assert batches, "actor shipped nothing"
    b0 = batches[0]
    seq = cfg.replay.seq_length
    assert b0["obs"].shape[1:] == (seq, 2)
    assert b0["actions"].shape[1:] == (seq,)
    assert b0["init_c"].shape[1:] == (lstm,)
    assert (b0["priorities"] > 0).all()
    assert (b0["mask"].sum(axis=1) >= 1).all()
    # frames are accounted separately from sequence counts
    assert sum(b["frames"] for b in batches) == 100
    # init states advance with the fake recurrence except at episode
    # starts (zeros)
    assert any(np.any(b["init_c"] != 0) for b in batches)


def test_sequence_learner_trains_and_updates_priorities():
    cfg = _r2d2_cfg()
    net = ApeXLSTMQNet(num_actions=2, lstm_size=8, dense=16,
                       compute_dtype="float32", mlp_torso=True)
    z = jnp.zeros((1, 8), jnp.float32)
    params = net.init(jax.random.key(0),
                      jnp.zeros((1, 4, 2), jnp.float32), (z, z))
    replay = PrioritizedReplay(capacity=64)
    spec = sequence_item_spec((2,), np.float32, 4, 8)
    lcfg = cfg.learner.__class__(batch_size=8, n_step=2, value_rescale=True,
                                 target_sync_every=10, lr=1e-3)
    rcfg = cfg.replay.__class__(seq_length=4, burn_in=1)
    learner = SingleChipLearner(
        r2d2_family(lambda p, o, s: net.apply(p, o, s), lcfg, rcfg),
        replay, lcfg)
    state = learner.init(params, replay.init(spec), jax.random.key(1))
    rng = np.random.default_rng(0)
    items = {
        "obs": jnp.asarray(rng.normal(size=(16, 4, 2)), jnp.float32),
        "actions": jnp.asarray(rng.integers(0, 2, (16, 4)), jnp.int32),
        "rewards": jnp.asarray(rng.normal(size=(16, 4)), jnp.float32),
        "terminals": jnp.zeros((16, 4), jnp.float32),
        "mask": jnp.ones((16, 4), jnp.float32),
        "init_c": jnp.zeros((16, 8), jnp.float32),
        "init_h": jnp.zeros((16, 8), jnp.float32),
    }
    state = learner.add(state, items, jnp.ones(16))
    assert int(state.replay.size) == 16
    tree_before = np.asarray(state.replay.tree).copy()
    state, m = learner.train_step(state)
    assert np.isfinite(m["loss"])
    assert int(state.step) == 1
    # priorities were written back into the sum-tree
    assert not np.allclose(np.asarray(state.replay.tree), tree_before)
    state, m = learner.train_many(state, 3)
    assert int(state.step) == 4
    assert np.isfinite(m["loss"]) and m["valid_frac"] > 0


def test_r2d2_driver_end_to_end():
    """Full recurrent wiring: recurrent actors -> batched stateful
    inference -> sequence ingest -> sequence learner -> recurrent eval."""
    cfg = _r2d2_cfg(num_actors=2).replace(eval_every_steps=50,
                                          eval_episodes=2)
    driver = ApexDriver(cfg)
    assert driver.family == "r2d2"
    out = driver.run(total_env_frames=2500, max_grad_steps=60,
                     wall_clock_limit_s=240)
    assert out["actor_errors"] == [], out["actor_errors"]
    assert out["loop_errors"] == [], out["loop_errors"]
    assert out["grad_steps"] >= 60, out
    assert out["frames"] >= 100, out
    assert out["episodes"] > 0
    assert driver.server.params_version > 0
    # the guaranteed end-of-training eval ran with the recurrent policy
    assert out["eval"] is not None and out["eval"]["episodes"] > 0


def test_r2d2_dist_driver_end_to_end():
    """Distributed R2D2 (SURVEY.md §2.1 config 4 attests dp=4 x tp=2):
    sequence-replay shards + LSTM sequence loss over the virtual
    8-device mesh, sequence round-robin ingest, replicated publication."""
    from ape_x_dqn_tpu.parallel.dist_learner import DistLearner

    cfg = _r2d2_cfg(num_actors=2).replace(
        parallel=ParallelConfig(dp=4, tp=2))
    driver = ApexDriver(cfg)
    assert driver.is_dist and driver.family == "r2d2"
    assert isinstance(driver.learner, DistLearner)
    assert driver.learner.family.name == "r2d2"
    out = driver.run(total_env_frames=2500, max_grad_steps=40,
                     wall_clock_limit_s=240)
    assert out["actor_errors"] == [], out["actor_errors"]
    assert out["loop_errors"] == [], out["loop_errors"]
    assert out["grad_steps"] >= 40, out
    assert driver.server.params_version > 0
    # every dp shard of the sequence replay received sequences
    sizes = np.asarray(driver.state.replay.size)
    assert sizes.shape == (4,) and (sizes > 0).all(), sizes


def _fake_pixel_episode(length, stack=4, h=6, w=6, seed=0):
    """Sliding-stack observations like the Atari wrapper produces:
    frame log [0]*3 + [f0, f1, ...]; obs_t = log[t:t+stack]."""
    rng = np.random.default_rng(seed)
    log = [np.zeros((h, w), np.uint8)] * (stack - 1)
    log += [rng.integers(0, 255, (h, w)).astype(np.uint8)
            for _ in range(length + 1)]
    return [np.stack(log[t:t + stack], axis=-1) for t in range(length + 1)]


def test_sequence_builder_frame_mode_matches_stacked():
    """Feeding the same episode, the frame-mode builder's sequences
    reconstruct to exactly the stacked builder's obs arrays."""
    from ape_x_dqn_tpu.replay.sequence import batch_to_sequence_batch

    seq, overlap, stack = 8, 4, 4
    flat_b = SequenceBuilder(seq, overlap, lstm_size=2)
    ring_b = SequenceBuilder(seq, overlap, lstm_size=2, frame_mode=True)
    obs_seq = _fake_pixel_episode(21, stack=stack)
    pre = (np.zeros(2, np.float32), np.zeros(2, np.float32))
    flat_items, ring_items = [], []
    for t in range(21):
        end = t == 20
        flat_items += flat_b.append(obs_seq[t], t % 4, 1.0, end, pre,
                                    td=1.0)
        ring_items += ring_b.append(obs_seq[t], t % 4, 1.0, end, pre,
                                    td=1.0)
    assert len(flat_items) == len(ring_items) > 1
    for fi, ri in zip(flat_items, ring_items):
        assert "obs" not in ri and "seq_frames" in ri
        assert ri["seq_frames"].shape == (seq + stack - 1, 6, 6)
        np.testing.assert_array_equal(fi["actions"], ri["actions"])
        np.testing.assert_array_equal(fi["mask"], ri["mask"])
        # device-side reconstruction == stacked storage, on live steps
        batch = {k: jnp.asarray(v)[None] for k, v in ri.items()
                 if k != "priority"}
        rebuilt = np.asarray(batch_to_sequence_batch(batch).obs[0])
        live = fi["mask"].astype(bool)
        np.testing.assert_array_equal(rebuilt[live], fi["obs"][live])


def test_r2d2_driver_end_to_end_frame_sequences_dist():
    """The full flagship R2D2 layout: pixel CNN-torso LSTM on the
    synthetic Atari env, FRAME-MODE sequence storage, sharded over the
    dp=4 x tp=2 virtual mesh — single-frame sequences round-robin
    through dist ingest, stacks rebuilt inside the sharded sequence-
    learner jit."""
    cfg = get_config("r2d2").replace(
        env=EnvConfig(id="catch", kind="synthetic_atari", resize=42,
                      max_noop_start=4),
        network=NetworkConfig(kind="lstm_q", lstm_size=32, torso_dense=64,
                              dueling=True, compute_dtype="float32"),
        replay=ReplayConfig(kind="sequence", capacity=256, seq_length=16,
                            seq_overlap=8, burn_in=4, min_fill=16,
                            storage="frame_ring"),
        learner=LearnerConfig(batch_size=8, n_step=3, value_rescale=True,
                              target_sync_every=100, lr=1e-3,
                              publish_every=10, train_chunk=2),
        actors=ActorConfig(num_actors=1, base_eps=0.4, ingest_batch=32),
        inference=InferenceConfig(max_batch=4, deadline_ms=1.0),
        parallel=ParallelConfig(dp=4, tp=2),
        eval_every_steps=0, eval_episodes=0,
    )
    driver = ApexDriver(cfg)
    assert driver.family == "r2d2" and driver.is_dist
    assert not driver._frame_mode  # segment staging is flat-family-only
    assert "seq_frames" in driver._item_spec
    out = driver.run(total_env_frames=1600, max_grad_steps=10,
                     wall_clock_limit_s=300)
    assert out["actor_errors"] == [], out["actor_errors"]
    assert out["loop_errors"] == [], out["loop_errors"]
    assert out["grad_steps"] >= 10, out
    assert driver.server.params_version > 0
    sizes = np.asarray(driver.state.replay.size)
    assert sizes.shape == (4,) and (sizes > 0).all(), sizes


def test_r2d2_frame_sequences_reject_vector_obs():
    """The frame_ring r2d2 preset on a vector-obs env must fail with a
    clear message at driver construction, not an unpack crash."""
    cfg = _r2d2_cfg()
    cfg = cfg.replace(replay=dataclasses.replace(cfg.replay,
                                                 storage="frame_ring"))
    with pytest.raises(ValueError, match="pixel obs"):
        ApexDriver(cfg)


@pytest.mark.slow
def test_r2d2_improves_masked_cartpole():
    """Reward slope on the POMDP task: the recurrent agent must beat the
    random plateau (~22 per episode) by a clear margin. Measured
    dynamics: behaviour avg return reaches ~60-70 inside 7 wall-clock
    minutes on the CPU test harness."""
    cfg = _r2d2_cfg(num_actors=2, lstm=64).replace(
        eval_every_steps=0, eval_episodes=10, total_env_frames=40_000)
    driver = ApexDriver(cfg)
    out = driver.run(max_grad_steps=10**9, wall_clock_limit_s=480)
    assert out["actor_errors"] == [] and out["loop_errors"] == []
    # the greedy recurrent eval is high-variance on this tiny task (single
    # episodes span 9..500); 10 episodes + a margin over the untrained
    # plateau (~22) keeps the slope assertion robust
    assert out["eval"] is not None
    assert out["eval"]["mean_return"] > 35, out["eval"]


def _seq_learner_with_items(sample_chunk=1, n_items=64, seed=0,
                            sample_prefetch=False):
    """Small r2d2-family learner + filled replay for mechanics tests."""
    net = ApeXLSTMQNet(num_actions=2, lstm_size=8, dense=16,
                       compute_dtype="float32", mlp_torso=True)
    z = jnp.zeros((1, 8), jnp.float32)
    params = net.init(jax.random.key(0),
                      jnp.zeros((1, 4, 2), jnp.float32), (z, z))
    replay = PrioritizedReplay(capacity=128)
    spec = sequence_item_spec((2,), np.float32, 4, 8)
    lcfg = LearnerConfig(batch_size=8, n_step=2, value_rescale=True,
                         target_sync_every=3, lr=1e-3,
                         sample_chunk=sample_chunk,
                         sample_prefetch=sample_prefetch)
    rcfg = ReplayConfig(kind="sequence", seq_length=4, burn_in=1)
    learner = SingleChipLearner(
        r2d2_family(lambda p, o, s: net.apply(p, o, s), lcfg, rcfg),
        replay, lcfg)
    state = learner.init(params, replay.init(spec), jax.random.key(1))
    rng = np.random.default_rng(seed)
    items = {
        "obs": jnp.asarray(rng.normal(size=(n_items, 4, 2)), jnp.float32),
        "actions": jnp.asarray(rng.integers(0, 2, (n_items, 4)), jnp.int32),
        "rewards": jnp.asarray(rng.normal(size=(n_items, 4)), jnp.float32),
        "terminals": jnp.zeros((n_items, 4), jnp.float32),
        "mask": jnp.ones((n_items, 4), jnp.float32),
        "init_c": jnp.zeros((n_items, 8), jnp.float32),
        "init_h": jnp.zeros((n_items, 8), jnp.float32),
    }
    state = learner.add(
        state, items,
        jnp.asarray(rng.random(n_items) + 0.1, jnp.float32))
    return learner, state


def test_sequence_kbatch_train_many_mechanics():
    """sample_chunk=K on the r2d2-family learner (round-5 verdict item 5):
    one stratified K*B sequence sample + one priority write-back per K
    grad-steps; step counts, the remainder path, target sync inside the
    macro-step, and tree repair must all hold — mirroring
    test_runtime.test_kbatch_train_many_mechanics for flat DQN."""
    learner, state = _seq_learner_with_items(sample_chunk=4)
    tree_before = np.asarray(state.replay.tree).copy()

    state, m = learner.train_many(state, 8)   # pure macro-steps
    assert int(state.step) == 8
    assert np.isfinite(m["loss"]) and m["valid_frac"] > 0
    assert np.asarray(state.replay.tree)[1] != tree_before[1]

    state, m = learner.train_many(state, 10)  # 2 exact + 2 macro-steps
    assert int(state.step) == 18
    assert np.isfinite(m["loss"])

    # step 18 is a sync boundary (sync_every=3): targets == online
    t = jax.tree.leaves(jax.tree.map(np.asarray, state.target_params))
    p = jax.tree.leaves(jax.tree.map(np.asarray, state.params))
    for a, b in zip(t, p):
        np.testing.assert_array_equal(a, b)


def test_sequence_kbatch_determinism():
    """Same seed, same params through the sequence K-batch path."""
    def run():
        learner, state = _seq_learner_with_items(sample_chunk=4, seed=3)
        state, _ = learner.train_many(state, 12)
        return jax.tree.map(np.asarray, state.params)
    a, b = run(), run()
    jax.tree.map(np.testing.assert_array_equal, a, b)


def test_sequence_prefetch_train_many_mechanics():
    """sample_prefetch on the r2d2-family learner: the double-buffered
    train_many pipeline (next chunk's sequence sample drawn before this
    chunk's priority write-back) holds the same step-count, remainder,
    and sync-boundary contract as the fused K-batch path, and its first
    macro-step is bit-identical to train_step_k (the prologue draw sees
    the same priorities the fused path would)."""
    learner, state = _seq_learner_with_items(sample_chunk=4,
                                             sample_prefetch=True)
    tree_before = np.asarray(state.replay.tree).copy()

    state, m = learner.train_many(state, 8)   # pure macro-steps
    assert int(state.step) == 8
    assert np.isfinite(m["loss"]) and m["valid_frac"] > 0
    assert np.asarray(state.replay.tree)[1] != tree_before[1]

    state, m = learner.train_many(state, 10)  # 2 exact + 2 macro-steps
    assert int(state.step) == 18
    assert np.isfinite(m["loss"])

    # step 18 is a sync boundary (sync_every=3): targets == online
    t = jax.tree.leaves(jax.tree.map(np.asarray, state.target_params))
    p = jax.tree.leaves(jax.tree.map(np.asarray, state.params))
    for a, b in zip(t, p):
        np.testing.assert_array_equal(a, b)

    # first-macro equivalence against the fused path
    l1, s1 = _seq_learner_with_items(sample_chunk=4, seed=2,
                                     sample_prefetch=True)
    l2, s2 = _seq_learner_with_items(sample_chunk=4, seed=2)
    s1, _ = l1.train_many(s1, 4)
    s2, _ = l2.train_step_k(s2, 4)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        s1.params, s2.params)
    np.testing.assert_array_equal(np.asarray(s1.replay.tree),
                                  np.asarray(s2.replay.tree))


def test_sequence_prefetch_determinism():
    """Same seed, same params through the sequence prefetch pipeline."""
    def run():
        learner, state = _seq_learner_with_items(sample_chunk=4, seed=3,
                                                 sample_prefetch=True)
        state, _ = learner.train_many(state, 12)
        return jax.tree.map(np.asarray, state.params)
    a, b = run(), run()
    jax.tree.map(np.testing.assert_array_equal, a, b)


@pytest.mark.slow
def test_r2d2_improves_masked_cartpole_prefetch():
    """Learning parity for the double-buffered sampler on the recurrent
    family: with sample_chunk=4 + sample_prefetch=True the masked
    CartPole agent must clear the same eval bar as the exact path
    (test_r2d2_improves_masked_cartpole) — the one-dispatch priority
    staleness must not cost learning on the POMDP task."""
    cfg = _r2d2_cfg(num_actors=2, lstm=64).replace(
        eval_every_steps=0, eval_episodes=10, total_env_frames=40_000)
    cfg = cfg.replace(learner=dataclasses.replace(
        cfg.learner, sample_chunk=4, sample_prefetch=True))
    driver = ApexDriver(cfg)
    out = driver.run(max_grad_steps=10**9, wall_clock_limit_s=480)
    assert out["actor_errors"] == [] and out["loop_errors"] == []
    assert out["eval"] is not None
    assert out["eval"]["mean_return"] > 35, out["eval"]


def test_dist_sequence_kbatch_train_step_k():
    """K-batch mechanics on the DIST sequence learner (round-4 advisor
    finding: the dist r2d2 learner inherited the K path with no test):
    the dp=4 x tp=2 driver trains with sample_chunk=4 through
    train_many, steps count correctly, and every shard's tree is
    repaired."""
    from ape_x_dqn_tpu.parallel.dist_learner import DistLearner

    cfg = _r2d2_cfg(num_actors=2).replace(
        parallel=ParallelConfig(dp=4, tp=2))
    cfg = cfg.replace(learner=dataclasses.replace(cfg.learner,
                                                  sample_chunk=4))
    driver = ApexDriver(cfg)
    assert isinstance(driver.learner, DistLearner)
    assert driver.learner.family.name == "r2d2"
    out = driver.run(total_env_frames=2500, max_grad_steps=40,
                     wall_clock_limit_s=240)
    assert out["actor_errors"] == [], out["actor_errors"]
    assert out["loop_errors"] == [], out["loop_errors"]
    assert out["grad_steps"] >= 40, out
    sizes = np.asarray(driver.state.replay.size)
    assert sizes.shape == (4,) and (sizes > 0).all(), sizes
