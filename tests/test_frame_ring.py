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


def _zero_query(obs, n):
    # greedy ties -> argmax 0, same both
    return np.zeros((n, 18), np.float32)


def test_actor_equivalence_flat_vs_frame_ring():
    """Identical env + seed + policy: the frame-ring actor's segments,
    reconstructed, must equal the flat actor's shipped transitions
    field-for-field (including pixels) in the same order."""
    flat_t, ring_t = _CaptureTransport(), _CaptureTransport()
    a_flat = Actor(_catch_cfg("flat"), 0, _zero_query, flat_t)
    a_ring = Actor(_catch_cfg("frame_ring"), 0, _zero_query, ring_t)
    assert (a_ring.cores[0].seg is not None
            and a_flat.cores[0].seg is None)
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


# -- the ring's rows are 32-bit words (PR 29) --------------------------------

# H*W = 63: not a multiple of 4, so the last word of a frame is partly pad
L_OBS = (7, 9, 4)
L_CAP, L_SEG = 64, 8


def _layout_ring(obs=L_OBS):
    return FrameRingReplay(capacity=L_CAP, seg_transitions=L_SEG,
                           n_step=N_STEP, obs_shape=obs)


def _stamped_segments(replay, g, serial0, rng):
    """g segments as the ingest stages them; every frame carries its
    4-byte little-endian serial in pixels 0-3 (the benchmark's stamp)
    over random pixels, so a frame fetched from the wrong row or a
    byte from the wrong lane of a word cannot pass."""
    f, b = replay.F, replay.B
    frames = rng.integers(0, 256, (g, f, replay.h * replay.w), np.uint8)
    serial = (serial0 + np.arange(g * f, dtype=np.uint32)).reshape(g, f)
    frames[..., :4] = serial[..., None].astype("<u4").view(np.uint8)
    items = {
        "seg_frames": frames.reshape(g, f, replay.h, replay.w),
        "action": rng.integers(0, 4, (g, b)).astype(np.int32),
        "reward": rng.normal(size=(g, b)).astype(np.float32),
        "discount": rng.uniform(0.5, 1.0, (g, b)).astype(np.float32),
        "next_off": rng.integers(1, N_STEP + 1, (g, b)).astype(np.int32),
    }
    pris = rng.uniform(0.1, 2.0, (g, b)).astype(np.float32)
    return items, pris


class _HostRing:
    """Numpy oracle of the ring built from the segments themselves:
    whole segments at the cursor with skip-to-head, or where told."""

    def __init__(self, replay):
        self.r = replay
        self.items = {}          # segment slot -> its staged fields
        self.pos = 0

    def write(self, items, seg0=None):
        g = items["action"].shape[0]
        if seg0 is None:
            seg0 = self.pos if self.pos + g <= self.r.S else 0
        for i in range(g):
            self.items[seg0 + i] = {k: v[i] for k, v in items.items()}
        self.pos = (seg0 + g) % self.r.S

    def transition(self, idx):
        seg, j = divmod(int(idx), self.r.B)
        it = self.items[seg]
        off = int(it["next_off"][j])
        st = self.r.stack
        return {"obs": np.moveaxis(it["seg_frames"][j:j + st], 0, -1),
                "next_obs": np.moveaxis(
                    it["seg_frames"][j + off:j + off + st], 0, -1),
                "action": it["action"][j], "reward": it["reward"][j],
                "discount": it["discount"][j]}

    def byte_rows(self):
        rows = np.zeros((self.r.S * self.r.F, self.r.frame_row), np.uint8)
        for seg, it in self.items.items():
            rows[seg * self.r.F:(seg + 1) * self.r.F, :self.r.frame_bytes] \
                = it["seg_frames"].reshape(self.r.F, -1)
        return rows


def _fill(case, obs=L_OBS):
    """-> (replay, device state, oracle) after the case's writes."""
    replay = _layout_ring(obs)
    rng = np.random.default_rng(29)
    state, host = replay.init(), _HostRing(replay)
    add = jax.jit(replay.add)
    add_at = jax.jit(replay.add_at)
    blocks = {"add": 2, "wrap": 3, "add_at": 2}[case]
    for n in range(blocks):         # 3 blocks of 3 in 8 slots: the third
        items, pris = _stamped_segments(replay, 3, 1000 * n, rng)  # wraps
        state = add(state, items, pris)
        host.write(items)
    if case == "wrap":
        assert int(state.pos) == 3 and host.pos == 3
    if case == "add_at":
        items, pris = _stamped_segments(replay, 2, 7000, rng)
        state = add_at(state, items, pris, jnp.int32(1))
        host.write(items, seg0=1)
    return replay, state, host


# a stack of 4 is rebuilt on words; any other depth takes the plain form
@pytest.mark.parametrize("case,chunks,obs", [
    *[(case, chunks, L_OBS) for case in ("add", "wrap", "add_at")
      for chunks in (1, 4)],
    ("wrap", 4, (7, 9, 3)), ("add_at", 1, (5, 5, 6))])
def test_word_rows_sample_byte_for_byte(case, chunks, obs):
    """sample_items over a ring of 32-bit-word rows: obs / next_obs /
    fields byte for byte the segments', idx / probs what the sum-tree
    alone decides (the draw never reads the frames, so they are the
    byte-row ring's for the same key)."""
    from ape_x_dqn_tpu.ops import sum_tree
    replay, state, host = _fill(case, obs)
    key, batch = jax.random.key(5), 32
    got, idx, probs = jax.jit(
        replay.sample_items, static_argnums=(2, 3))(state, key, batch,
                                                    chunks)
    want_idx, want_probs = sum_tree.sample(state.tree, key, batch,
                                           size=state.size, chunks=chunks)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_array_equal(np.asarray(probs), np.asarray(want_probs))
    assert got["obs"].dtype == jnp.uint8
    assert got["obs"].shape == got["next_obs"].shape == (batch, *obs)
    want = [host.transition(i) for i in np.asarray(idx)]
    for k in ("obs", "next_obs", "action", "reward", "discount"):
        np.testing.assert_array_equal(
            np.asarray(got[k]), np.stack([t[k] for t in want]), err_msg=k)


@pytest.mark.parametrize("case", ("add", "wrap", "add_at"))
def test_word_rows_hold_the_frames_bytes_in_order(case):
    """storage["frames"] is uint32 [S*F, frame_row // 4]; read as bytes
    (least significant first) it is the padded byte row of each frame,
    and utils/hbm.py prices it as it priced the byte rows."""
    from ape_x_dqn_tpu.utils import hbm
    replay, state, host = _fill(case)
    frames = state.storage["frames"]
    assert frames.dtype == jnp.uint32
    assert frames.shape == (replay.S * replay.F, replay.frame_row // 4)
    words = np.asarray(frames)
    got = np.stack([(words >> (8 * i)) & 0xFF for i in range(4)],
                   axis=-1).astype(np.uint8).reshape(words.shape[0], -1)
    np.testing.assert_array_equal(got, host.byte_rows())
    priced, detail = hbm._frame_ring_bytes(L_CAP, L_SEG, N_STEP, L_OBS)
    assert detail["frame_rows"] * detail["frame_row_bytes"] \
        == frames.nbytes == replay.S * replay.F * 128
    assert priced == sum(x.nbytes for x in state.storage.values())


def test_word_rows_read_region_restages_bit_identically():
    """read_region hands back uint8 segments (the cold tier's unit) and
    add_at of them rewrites the same words."""
    replay, state, host = _fill("add_at")
    items, pri = jax.jit(replay.read_region, static_argnums=2)(
        state, jnp.int32(1), 3)
    assert items["seg_frames"].dtype == jnp.uint8
    assert items["seg_frames"].shape == (3, replay.F, replay.h, replay.w)
    for i in range(3):
        for k, v in host.items[1 + i].items():
            np.testing.assert_array_equal(np.asarray(items[k][i]), v,
                                          err_msg=k)
    # restage into an emptied copy of the region
    blank = jax.tree.map(jnp.zeros_like, items)
    wiped = replay.add_at(state, blank, jnp.zeros_like(pri), jnp.int32(1))
    assert not np.array_equal(np.asarray(wiped.storage["frames"]),
                              np.asarray(state.storage["frames"]))
    back = replay.add_at(wiped, items, jnp.zeros_like(pri), jnp.int32(1))
    for k in state.storage:
        np.testing.assert_array_equal(np.asarray(back.storage[k]),
                                      np.asarray(state.storage[k]),
                                      err_msg=k)


@pytest.mark.parametrize("write", ("add", "add_at", "add_lockstep",
                                   "add_at_lockstep"))
def test_no_byte_block_reaches_the_word_ring(write, monkeypatch):
    """dus_rows converts VALUES (block.astype(buf.dtype)): a uint8
    block handed to it for the uint32 ring would store one pixel per
    word. Every write packs its bytes into words first, so each block
    already has its buffer's dtype when the ring write sees it."""
    from ape_x_dqn_tpu.replay import frame_ring
    seen = []

    def checked(real):
        def write_rows(buf, block, *args, **kw):
            seen.append((buf.dtype, block.dtype))
            return real(buf, block, *args, **kw)
        return write_rows

    monkeypatch.setattr(frame_ring, "dus_rows",
                        checked(frame_ring.dus_rows))
    monkeypatch.setattr(frame_ring, "dus_rows_per_shard",
                        checked(frame_ring.dus_rows_per_shard))
    replay = _layout_ring()
    items, pris = _stamped_segments(replay, 2, 0, np.random.default_rng(1))
    args = [replay.init(), items, pris]
    if "lockstep" in write:
        args = [jax.tree.map(lambda x: jnp.stack([x, x]), a) for a in args]
    if "at" in write:
        args.append(jnp.asarray([1, 4], jnp.int32) if "lockstep" in write
                    else jnp.int32(1))
    getattr(replay, write)(*args)
    assert (jnp.uint32, jnp.uint32) in seen       # the frames leaf
    assert all(buf == block for buf, block in seen), seen


def test_word_ring_is_written_in_place():
    """The compiled add aliases the donated ring: no temporary the size
    of the frames leaf (the byte rows' add temp 0, PERF.md)."""
    replay = FrameRingReplay(capacity=4096, seg_transitions=16,
                             n_step=N_STEP, obs_shape=(84, 84, 4))
    items, pris = _stamped_segments(replay, 4, 0, np.random.default_rng(2))
    shapes = jax.eval_shape(replay.init)
    ring_bytes = shapes.storage["frames"].size * 4
    compiled = jax.jit(replay.add, donate_argnums=0).lower(
        shapes, items, pris).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < ring_bytes // 4, (
        mem.temp_size_in_bytes, ring_bytes)
    assert mem.alias_size_in_bytes >= ring_bytes
