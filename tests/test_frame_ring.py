"""Frame-ring replay (replay/frame_ring.py): segment assembly, device
reconstruction, learner integration, and flat-vs-frame actor equivalence
(SURVEY.md §7 hard part 2 "ingest bandwidth"; §2.2 replay capacity)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.configs import (
    ActorConfig, EnvConfig, InferenceConfig, LearnerConfig, NetworkConfig,
    ReplayConfig, RunConfig)
from ape_x_dqn_tpu.envs import make_env
from ape_x_dqn_tpu.replay.frame_ring import (
    FrameRingReplay, FrameSegmentBuilder, frame_segment_spec)
from ape_x_dqn_tpu.runtime.actor import Actor


H = W = 6
STACK = 4
N_STEP = 3
B = 4  # tiny segments so episode-end padding is exercised often


def _frame(i):
    """Distinct deterministic frame per step index."""
    return np.full((H, W), i % 251, np.uint8)


class _ScriptedEpisodes:
    """Feeds the builder like an actor would, tracking the oracle frame
    log host-side so reconstructions can be checked exactly."""

    def __init__(self, builder: FrameSegmentBuilder):
        self.b = builder
        self.oracle = {}  # global transition counter -> (obs, next_obs)
        self.meta = {}    # counter -> (action, reward, discount)
        self.count = 0

    def run_episode(self, length: int, first_frame: int,
                    spans=None) -> None:
        # wrapper semantics: full reset -> zero-padded stack
        log = [np.zeros((H, W), np.uint8)] * (STACK - 1) \
            + [_frame(first_frame)]
        reset_obs = np.stack(log, axis=-1)
        self.b.on_reset(reset_obs)
        for t in range(length):
            log.append(_frame(first_frame + t + 1))
            self.b.on_step(np.stack(log[-STACK:], axis=-1))
        # emit transitions in start order with the episode's spans
        for t in range(length):
            span = (spans[t] if spans is not None
                    else min(N_STEP, length - t))
            if t + span > length:
                span = length - t
            action, reward, disc = t % 4, float(t), 0.5  # 4 = test env's
            # num_actions: out-of-range actions NaN the gathered Q
            self.b.add(action, reward, disc, span, priority=1.0 + t)
            obs = np.stack(log[t:t + STACK], axis=-1)
            nxt = np.stack(log[t + span:t + span + STACK], axis=-1)
            self.meta[self.count] = (action, reward, disc)
            self.oracle[self.count] = (obs, nxt)
            self.count += 1


def test_segment_builder_shapes_and_padding():
    b = FrameSegmentBuilder(B, N_STEP, STACK)
    s = _ScriptedEpisodes(b)
    s.run_episode(length=6, first_frame=10)  # 6 = B + 2 -> one pad segment
    segs = b.flush()
    assert len(segs) == 2
    F = B + N_STEP + STACK - 1
    for seg in segs:
        assert seg["seg_frames"].shape == (1, F, H, W)
        assert seg["action"].shape == (1, B)
    # second segment: 2 live + 2 dead pads
    assert list(segs[1]["next_off"][0] > 0) == [True, True, False, False]
    assert list(segs[1]["priorities"][0][2:]) == [0.0, 0.0]


def test_device_reconstruction_matches_oracle():
    """Every stack rebuilt on device equals the actor-side stack it
    encodes — across segment padding, short episodes, and ring wrap."""
    replay = FrameRingReplay(capacity=32, seg_transitions=B, n_step=N_STEP,
                             obs_shape=(H, W, STACK))
    state = replay.init()
    b = FrameSegmentBuilder(B, N_STEP, STACK)
    s = _ScriptedEpisodes(b)
    s.run_episode(length=6, first_frame=10)
    s.run_episode(length=3, first_frame=50)   # shorter than B
    s.run_episode(length=9, first_frame=100)
    segs = b.flush()

    slot = {}  # transition slot -> oracle counter
    counter = 0
    for gseg, seg in enumerate(segs):
        items = {k: jnp.asarray(seg[k]) for k in
                 ("seg_frames", "action", "reward", "discount", "next_off")}
        state = replay.add(state, items, jnp.asarray(seg["priorities"]))
        for j in range(B):
            if seg["next_off"][0][j] > 0:
                slot[gseg * B + j] = counter
                counter += 1
    assert counter == s.count

    idx = jnp.asarray(sorted(slot), jnp.int32)
    got = replay._gather(state, idx)
    for row, i in enumerate(sorted(slot)):
        obs, nxt = s.oracle[slot[i]]
        action, reward, disc = s.meta[slot[i]]
        np.testing.assert_array_equal(np.asarray(got["obs"][row]), obs,
                                      err_msg=f"obs slot {i}")
        np.testing.assert_array_equal(np.asarray(got["next_obs"][row]), nxt,
                                      err_msg=f"next_obs slot {i}")
        assert int(got["action"][row]) == action
        assert float(got["reward"][row]) == reward
        assert float(got["discount"][row]) == disc


def test_ring_wrap_overwrites_whole_segments():
    replay = FrameRingReplay(capacity=8, seg_transitions=4, n_step=N_STEP,
                             obs_shape=(H, W, STACK))  # S = 2 segments
    state = replay.init()
    b = FrameSegmentBuilder(4, N_STEP, STACK)
    s = _ScriptedEpisodes(b)
    s.run_episode(length=12, first_frame=0)  # 3 segments -> wraps
    segs = b.flush()
    for seg in segs:
        items = {k: jnp.asarray(seg[k]) for k in
                 ("seg_frames", "action", "reward", "discount", "next_off")}
        state = replay.add(state, items, jnp.asarray(seg["priorities"]))
    assert int(state.size) == 8
    assert int(state.pos) == 1  # 3 segments into 2 slots
    # slot 0 now holds the THIRD segment (starts 8..11)
    got = replay._gather(state, jnp.asarray([0], jnp.int32))
    obs, _ = s.oracle[8]
    np.testing.assert_array_equal(np.asarray(got["obs"][0]), obs)


def test_dead_slots_never_sampled_and_stay_dead():
    replay = FrameRingReplay(capacity=8, seg_transitions=4, n_step=N_STEP,
                             obs_shape=(H, W, STACK))
    state = replay.init()
    b = FrameSegmentBuilder(4, N_STEP, STACK)
    s = _ScriptedEpisodes(b)
    s.run_episode(length=2, first_frame=0)  # 2 live + 2 dead in segment 0
    (seg,) = b.flush()
    items = {k: jnp.asarray(seg[k]) for k in
             ("seg_frames", "action", "reward", "discount", "next_off")}
    state = replay.add(state, items, jnp.asarray(seg["priorities"]))
    _, idx, w = replay.sample(state, jax.random.key(0), 256)
    assert np.all(np.asarray(idx) <= 1), "sampled a dead/pad slot"
    assert np.all(np.asarray(w) > 0)
    # priority write-back at a dead slot must not resurrect it
    state2 = replay.update_priorities(
        state, jnp.asarray([2, 3], jnp.int32),
        jnp.asarray([9.9, 9.9], jnp.float32))
    leaves = np.asarray(state2.tree[8:])
    assert leaves[2] == 0.0 and leaves[3] == 0.0


def test_learner_runs_on_frame_ring():
    """dqn-family learner train_step over frame-ring storage: loss finite,
    priorities written back, donation-safe."""
    from ape_x_dqn_tpu.envs.base import EnvSpec
    from ape_x_dqn_tpu.models import build_network
    from ape_x_dqn_tpu.runtime.family import dqn_family
    from ape_x_dqn_tpu.runtime.learner import SingleChipLearner
    from ape_x_dqn_tpu.utils.rng import component_key

    spec = EnvSpec(obs_shape=(H, W, STACK), obs_dtype=np.dtype(np.uint8),
                   discrete=True, num_actions=4)
    net = build_network(NetworkConfig(kind="mlp", mlp_hidden=(16,),
                                      dueling=False,
                                      compute_dtype="float32"), spec)
    params = net.init(component_key(0, "net_init"),
                      jnp.zeros((1, H, W, STACK), jnp.uint8))
    replay = FrameRingReplay(capacity=64, seg_transitions=B, n_step=N_STEP,
                             obs_shape=(H, W, STACK))
    lcfg = LearnerConfig(batch_size=16, n_step=N_STEP,
                         target_sync_every=10)
    learner = SingleChipLearner(
        dqn_family(net.apply, lcfg), replay, lcfg)
    state = learner.init(params, replay.init(), component_key(0, "learner"))

    b = FrameSegmentBuilder(B, N_STEP, STACK)
    s = _ScriptedEpisodes(b)
    for e in range(8):
        s.run_episode(length=8, first_frame=e * 16)
    for seg in b.flush():
        items = {k: jnp.asarray(seg[k]) for k in
                 ("seg_frames", "action", "reward", "discount", "next_off")}
        state = learner.add(state, items, jnp.asarray(seg["priorities"]))
    assert int(state.replay.size) == 64
    tree_before = np.asarray(state.replay.tree).copy()
    state, m = learner.train_step(state)
    assert np.isfinite(float(m["loss"]))
    assert not np.array_equal(np.asarray(state.replay.tree), tree_before), \
        "train_step must write back updated priorities"
    state, m = learner.train_many(state, 3)
    assert np.isfinite(float(m["loss"]))


# -- actor equivalence: the gold test ---------------------------------------


def _catch_cfg(storage: str) -> RunConfig:
    return RunConfig(
        name="catch",
        env=EnvConfig(id="catch", kind="synthetic_atari", frame_skip=4,
                      max_noop_start=4),
        network=NetworkConfig(kind="nature_cnn", dueling=True),
        replay=ReplayConfig(kind="prioritized", capacity=4096, min_fill=128,
                            storage=storage, seg_transitions=8,
                            segs_per_add=2),
        learner=LearnerConfig(batch_size=32, n_step=N_STEP,
                              target_sync_every=100, publish_every=20),
        actors=ActorConfig(num_actors=1, base_eps=0.5, ingest_batch=8),
        inference=InferenceConfig(max_batch=4, deadline_ms=0.5),
        eval_every_steps=0, eval_episodes=0,
    )


class _CaptureTransport:
    def __init__(self):
        self.batches = []

    def send_experience(self, batch):
        self.batches.append(batch)


def _zero_query(obs):
    return np.zeros(18, np.float32)  # greedy ties -> argmax 0, same both


def test_actor_equivalence_flat_vs_frame_ring():
    """Identical env + seed + policy: the frame-ring actor's segments,
    reconstructed, must equal the flat actor's shipped transitions
    field-for-field (including pixels) in the same order."""
    flat_t, ring_t = _CaptureTransport(), _CaptureTransport()
    a_flat = Actor(_catch_cfg("flat"), 0, _zero_query, flat_t)
    a_ring = Actor(_catch_cfg("frame_ring"), 0, _zero_query, ring_t)
    assert a_ring._seg is not None and a_flat._seg is None
    a_flat.run(max_frames=150)
    a_ring.run(max_frames=150)

    # flatten the flat actor's stream
    flat = {k: np.concatenate([b[k] for b in flat_t.batches])
            for k in ("obs", "action", "reward", "next_obs", "discount",
                      "priorities")}

    # reconstruct the ring actor's stream through the real device path
    replay = FrameRingReplay(capacity=1024, seg_transitions=8,
                             n_step=N_STEP, obs_shape=(84, 84, 4))
    state = replay.init()
    order = []  # global transition idx in ship order
    for g, seg in enumerate(ring_t.batches):
        items = {k: jnp.asarray(seg[k]) for k in
                 ("seg_frames", "action", "reward", "discount", "next_off")}
        state = replay.add(state, items, jnp.asarray(seg["priorities"]))
        order.extend(g * 8 + j for j in range(8)
                     if seg["next_off"][0][j] > 0)
    assert len(order) == flat["action"].shape[0], \
        "live transition counts differ"
    got = replay._gather(state, jnp.asarray(order, jnp.int32))
    np.testing.assert_array_equal(np.asarray(got["action"]), flat["action"])
    np.testing.assert_allclose(np.asarray(got["reward"]), flat["reward"],
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got["discount"]),
                               flat["discount"], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got["obs"]), flat["obs"])
    np.testing.assert_array_equal(np.asarray(got["next_obs"]),
                                  flat["next_obs"])
    # priorities ship identically too (dead pads excluded)
    ring_pris = np.concatenate(
        [seg["priorities"][0][np.asarray(seg["next_off"][0]) > 0]
         for seg in ring_t.batches])
    np.testing.assert_allclose(ring_pris, flat["priorities"], rtol=1e-6)


def test_frame_segment_spec_shapes():
    spec = frame_segment_spec(16, 3, (84, 84, 4), np.uint8)
    assert spec["seg_frames"].shape == (22, 84, 84)
    assert spec["action"].shape == (16,)


def test_apex_driver_end_to_end_frame_ring():
    """Full wiring over frame-ring storage: actors ship frame segments,
    ingest stages whole segments, the learner trains off reconstructed
    stacks — no errors, params published."""
    from ape_x_dqn_tpu.runtime.driver import ApexDriver

    cfg = _catch_cfg("frame_ring")
    driver = ApexDriver(cfg)
    assert driver._frame_mode
    out = driver.run(total_env_frames=1200, max_grad_steps=40,
                     wall_clock_limit_s=180)
    assert out["actor_errors"] == [], out["actor_errors"]
    assert out["loop_errors"] == [], out["loop_errors"]
    assert out["grad_steps"] >= 40, out
    # min_fill counts transitions (pads included), so env frames at the
    # moment training starts can sit just under it
    assert out["frames"] >= 100, out
    assert driver.server.params_version > 0


def test_apex_dist_driver_end_to_end_frame_ring():
    """The flagship layout (frame-ring replay shards over a dp=4 x tp=2
    mesh, segment round-robin across shards) end to end on the virtual
    8-device mesh."""
    from ape_x_dqn_tpu.configs import ParallelConfig
    from ape_x_dqn_tpu.runtime.driver import ApexDriver

    cfg = _catch_cfg("frame_ring")
    cfg = cfg.replace(
        parallel=ParallelConfig(dp=4, tp=2),
        # 42x42 frames (conv pyramid stays valid) keep the 8-virtual-
        # device CPU compile + step cost inside the test budget
        env=dataclasses.replace(cfg.env, resize=42))
    driver = ApexDriver(cfg)
    assert driver.is_dist and driver._frame_mode
    out = driver.run(total_env_frames=2400, max_grad_steps=30,
                     wall_clock_limit_s=240)
    assert out["actor_errors"] == [], out["actor_errors"]
    assert out["loop_errors"] == [], out["loop_errors"]
    assert out["grad_steps"] >= 30, out
    sizes = np.asarray(driver.state.replay.size)
    assert sizes.shape == (4,) and (sizes > 0).all(), sizes


def test_driver_rejects_frame_ring_for_non_dqn():
    from ape_x_dqn_tpu.runtime.driver import ApexDriver
    from ape_x_dqn_tpu.configs import get_config
    cfg = get_config("apex_dpg")
    cfg = cfg.replace(replay=dataclasses.replace(cfg.replay,
                                                 storage="frame_ring"))
    with pytest.raises(NotImplementedError):
        ApexDriver(cfg)
