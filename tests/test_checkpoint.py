"""Checkpoint/resume: Orbax round-trip and driver resume continuity
(SURVEY.md §5 "Checkpoint / resume")."""

import jax
import numpy as np

from ape_x_dqn_tpu.configs import (
    ActorConfig, InferenceConfig, LearnerConfig, ReplayConfig, get_config)
from ape_x_dqn_tpu.runtime.driver import ApexDriver
from ape_x_dqn_tpu.utils.checkpoint import CheckpointManager


def _ckpt_cfg(tmp_path, **kw):
    return get_config("cartpole_smoke").replace(
        actors=ActorConfig(num_actors=1, base_eps=0.6, ingest_batch=16),
        replay=ReplayConfig(kind="prioritized", capacity=2048, min_fill=64),
        learner=LearnerConfig(batch_size=32, n_step=3,
                              target_sync_every=100, publish_every=20),
        inference=InferenceConfig(max_batch=8, deadline_ms=1.0),
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_every=20,
        eval_every_steps=0, eval_episodes=0,
        **kw)


def test_checkpoint_manager_roundtrip(tmp_path):
    mngr = CheckpointManager(str(tmp_path / "m"))
    payload = {"params": {"w": np.arange(6, dtype=np.float32).reshape(2, 3)},
               "step": np.int32(7)}
    mngr.save(7, payload, wait=True)
    assert mngr.latest_step() == 7
    got = mngr.restore(template=jax.tree.map(np.zeros_like, payload))
    np.testing.assert_array_equal(got["params"]["w"], payload["params"]["w"])
    assert int(got["step"]) == 7
    mngr.close()


def test_driver_saves_and_resumes(tmp_path):
    cfg = _ckpt_cfg(tmp_path)
    d1 = ApexDriver(cfg)
    out1 = d1.run(total_env_frames=1500, max_grad_steps=50,
                  wall_clock_limit_s=120)
    assert out1["actor_errors"] == [] and out1["loop_errors"] == []
    assert out1["grad_steps"] >= 50
    assert d1.ckpt.latest_step() == out1["grad_steps"]
    final_params = jax.tree.map(np.asarray, d1.state.params)

    # a fresh driver restores the latest checkpoint bitwise and resumes
    # the grad-step counter
    d2 = ApexDriver(cfg)
    assert d2._grad_steps_total == out1["grad_steps"]
    restored = jax.tree.map(np.asarray, d2.state.params)
    jax.tree.map(np.testing.assert_array_equal, final_params, restored)
    # restored params were published to the fresh inference server
    assert d2.server.params_version == out1["grad_steps"]

    # the resumed run continues to an ABSOLUTE grad-step target
    out2 = d2.run(total_env_frames=1500,
                  max_grad_steps=out1["grad_steps"] + 20,
                  wall_clock_limit_s=120)
    assert out2["actor_errors"] == [] and out2["loop_errors"] == []
    assert out2["grad_steps"] >= out1["grad_steps"] + 20
    assert d2.ckpt.latest_step() == out2["grad_steps"]


def test_replay_contents_checkpoint_skips_min_fill(tmp_path):
    """Opt-in replay checkpointing (SURVEY.md §5 'and (optionally)
    replay contents'): a resumed driver restores the device ReplayState
    and can train IMMEDIATELY — no re-ingest, no min_fill stall."""
    cfg = _ckpt_cfg(tmp_path, checkpoint_replay=True)
    d1 = ApexDriver(cfg)
    out1 = d1.run(total_env_frames=1500, max_grad_steps=50,
                  wall_clock_limit_s=120)
    assert out1["actor_errors"] == [] and out1["loop_errors"] == []
    filled1 = d1._replay_filled
    assert filled1 >= cfg.replay.min_fill
    tree1 = np.asarray(d1.state.replay.tree)

    d2 = ApexDriver(cfg)
    try:
        # the restored fill mirror already clears min_fill: the learner
        # loop would dispatch on its first iteration without any ingest
        assert d2._replay_filled == filled1
        assert d2._replay_filled >= d2._min_fill()
        # device replay state round-trips bitwise (sum-tree included)
        np.testing.assert_array_equal(np.asarray(d2.state.replay.tree),
                                      tree1)
        # and training off the restored contents actually works
        state, m = d2.learner.train_step(d2.state)
        assert np.isfinite(float(m["loss"]))
    finally:
        d2.server.stop()


def test_checkpoint_replay_flag_toggle_does_not_brick_resume(tmp_path):
    """checkpoint_replay governs SAVES; restores follow what the file
    contains — toggling the flag between runs must neither crash the
    Orbax template restore nor lose the saved replay contents."""
    cfg_off = _ckpt_cfg(tmp_path)
    d1 = ApexDriver(cfg_off)
    out1 = d1.run(total_env_frames=1500, max_grad_steps=40,
                  wall_clock_limit_s=120)
    assert out1["actor_errors"] == [] and out1["loop_errors"] == []

    # replay-less checkpoint, flag now ON: restore must not mismatch
    cfg_on = cfg_off.replace(checkpoint_replay=True)
    d2 = ApexDriver(cfg_on)
    assert d2._grad_steps_total == out1["grad_steps"]
    out2 = d2.run(total_env_frames=1500,
                  max_grad_steps=out1["grad_steps"] + 20,
                  wall_clock_limit_s=120)
    assert out2["actor_errors"] == [] and out2["loop_errors"] == []

    # d2's final save carried replay; flag OFF again: the contents
    # still restore (and future saves would drop them)
    d3 = ApexDriver(cfg_off)
    try:
        assert d3._grad_steps_total == out2["grad_steps"]
        assert d3._replay_filled > 0
    finally:
        d3.server.stop()


def test_multihost_rejects_checkpoint_replay():
    """The multihost driver must reject checkpoint_replay loudly (a
    silent no-op would break the config's resume promise). The gate
    sits before the process-count check so it is unit-testable."""
    import pytest

    from ape_x_dqn_tpu.configs import get_config
    from ape_x_dqn_tpu.runtime.multihost_driver import MultihostApexDriver

    cfg = get_config("cartpole_smoke").replace(checkpoint_replay=True)
    with pytest.raises(NotImplementedError, match="single-host only"):
        MultihostApexDriver(cfg)


def test_driver_without_checkpoint_dir_has_no_manager():
    cfg = get_config("cartpole_smoke").replace(
        actors=ActorConfig(num_actors=1),
        inference=InferenceConfig(max_batch=8, deadline_ms=1.0))
    d = ApexDriver(cfg)
    try:
        assert d.ckpt is None
    finally:
        d.server.stop()


def test_checkpoint_layout_version_stamp_transparent(tmp_path):
    """Every dict payload carries a storage-layout version stamp on
    disk, yet callers never see it: restore() strips it after checking,
    and item_keys() excludes it (the driver builds restore templates
    from item_keys, so the stamp must stay invisible there)."""
    import pytest

    from ape_x_dqn_tpu.utils import checkpoint as ckpt_mod

    mngr = CheckpointManager(str(tmp_path / "m"))
    payload = {"params": {"w": np.ones((2, 3), np.float32)},
               "step": np.asarray(5, np.int32)}
    mngr.save(5, payload, wait=True)

    # the stamp IS on disk...
    raw = mngr._raw_item_keys(5)
    assert raw is not None and ckpt_mod._LAYOUT_KEY in raw
    # ...but item_keys() (the driver's template source) never shows it
    assert mngr.item_keys(5) == {"params", "step"}
    # ...and restore() strips it from the returned payload
    got = mngr.restore(template=jax.tree.map(np.zeros_like, payload))
    assert ckpt_mod._LAYOUT_KEY not in got
    np.testing.assert_array_equal(got["params"]["w"], payload["params"]["w"])

    # a version mismatch fails loudly WITH the recovery guidance
    mngr.save(6, {**payload,
                  ckpt_mod._LAYOUT_KEY: np.asarray(999, np.int32)},
              wait=True)
    with pytest.raises(RuntimeError, match="storage layout v999"):
        mngr.restore(step=6, template=jax.tree.map(np.zeros_like, payload))
    mngr.close()


def test_replay_checkpoint_of_the_byte_row_ring_is_refused(tmp_path):
    """A replay-bearing payload stamped v2 (the frame ring's rows were
    uint8 bytes; they are uint32 words since v3) is refused with the
    recovery guidance, whatever its leaves would restore into."""
    import pytest

    from ape_x_dqn_tpu.replay.frame_ring import FrameRingReplay
    from ape_x_dqn_tpu.utils import checkpoint as ckpt_mod

    replay = FrameRingReplay(capacity=32, seg_transitions=8, n_step=3,
                             obs_shape=(12, 12, 4))
    state = jax.tree.map(np.asarray, replay.init()._asdict())
    payload = {"replay": state, "step": np.asarray(1, np.int32)}
    mngr = CheckpointManager(str(tmp_path / "m"))
    mngr.save(1, {**payload,
                  ckpt_mod._LAYOUT_KEY: np.asarray(2, np.int32)},
              wait=True)
    with pytest.raises(RuntimeError) as err:
        mngr.restore(step=1, template=jax.tree.map(np.zeros_like, payload))
    assert "storage layout v2" in str(err.value)
    assert ckpt_mod._LAYOUT_GUIDANCE in str(err.value)
    # the same payload under this code's own stamp restores
    mngr.save(2, payload, wait=True)
    got = mngr.restore(step=2, template=jax.tree.map(np.zeros_like, payload))
    assert got["replay"]["storage"]["frames"].dtype == np.uint32
    mngr.close()


def test_checkpoint_structure_mismatch_guidance(tmp_path):
    """An Orbax structure mismatch (e.g. a replay-bearing checkpoint
    written under the pre-versioning layout restored into new-layout
    shapes) surfaces as a RuntimeError carrying the documented recovery
    guidance, not a raw Orbax traceback."""
    import pytest

    mngr = CheckpointManager(str(tmp_path / "m"))
    mngr.save(3, {"params": {"w": np.ones((4, 4), np.float32)},
                  "step": np.asarray(3, np.int32)}, wait=True)
    bad_template = {"params": {"w": np.zeros((4, 4), np.float32)},
                    "replay_frames": np.zeros((8, 128), np.uint8),
                    "step": np.asarray(0, np.int32)}
    with pytest.raises(RuntimeError, match="restart the run fresh"):
        mngr.restore(step=3, template=bad_template)
    mngr.close()
