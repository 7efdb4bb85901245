import numpy as np
import pytest

from ape_x_dqn_tpu.configs import EnvConfig
from ape_x_dqn_tpu.envs import make_env, SyncVectorEnv
from ape_x_dqn_tpu.envs.atari import (
    AtariPreprocessing, SyntheticAtari, bilinear_resize, grayscale)
from ape_x_dqn_tpu.envs.cartpole import CartPole
from ape_x_dqn_tpu.envs.control import PendulumSwingUp


def test_cartpole_shapes_and_episode():
    env = CartPole(seed=0)
    obs = env.reset()
    assert obs.shape == (4,) and obs.dtype == np.float32
    total, steps, done = 0.0, 0, False
    while not done:
        obs, r, done, info = env.step(steps % 2)
        total += r
        steps += 1
        assert steps <= 500
    assert info["episode_return"] == total
    # alternating actions should fail well before the 500-step cap
    assert info["terminal"] or steps == 500


def test_cartpole_determinism():
    a, b = CartPole(seed=3), CartPole(seed=3)
    oa, ob = a.reset(), b.reset()
    np.testing.assert_array_equal(oa, ob)
    for t in range(50):
        ra = a.step(t % 2)
        rb = b.step(t % 2)
        np.testing.assert_array_equal(ra[0], rb[0])
        if ra[2]:
            break


def test_bilinear_resize_constant_and_range():
    img = np.full((210, 160), 117.0)
    out = bilinear_resize(img, 84, 84)
    assert out.shape == (84, 84)
    np.testing.assert_allclose(out, 117.0, atol=1e-4)
    grad = np.tile(np.arange(160, dtype=np.float32), (210, 1))
    outg = bilinear_resize(grad, 84, 84)
    assert outg.min() >= 0 and outg.max() <= 159
    assert outg[0, -1] > outg[0, 0]  # preserves monotone gradient


def test_synthetic_atari_raw():
    raw = SyntheticAtari(seed=0)
    frame = raw.reset()
    assert frame.shape == (210, 160, 3) and frame.dtype == np.uint8
    assert raw.lives == 5
    # ball is drawn on even raw frames, absent on odd ones (flicker)
    f1, _, _ = raw.step(0)  # frame_count 1 (odd) -> no ball
    f2, _, _ = raw.step(0)  # frame_count 2 (even) -> ball
    assert (f2 == 236).sum() > (f1 == 236).sum()


def test_synthetic_atari_episode_ends():
    raw = SyntheticAtari(seed=1)
    raw.reset()
    done, total_r, steps = False, 0.0, 0
    while not done:
        frame, r, done = raw.step(0)  # never move: will miss often
        total_r += r
        steps += 1
        assert steps < 100_000
    assert raw.lives == 0


def test_atari_preprocessing_pipeline():
    cfg = EnvConfig(id="PongNoFrameskip-v4", kind="atari")
    env = make_env(cfg, seed=0)
    obs = env.reset()
    assert obs.shape == (84, 84, 4) and obs.dtype == np.uint8
    assert env.spec.num_actions == 6
    obs2, r, done, info = env.step(0)
    assert obs2.shape == (84, 84, 4)
    assert r in (-1.0, 0.0, 1.0)  # clipped
    assert "lives" in info and "terminal" in info
    # frame stack shifts by one plane per step
    obs3, _, _, _ = env.step(0)
    np.testing.assert_array_equal(obs3[..., 2], obs2[..., 3])
    np.testing.assert_array_equal(obs3[..., 1], obs2[..., 2])


def test_atari_maxpool_defeats_flicker():
    """With frame-skip+max-pool the ball must be visible in every obs."""
    cfg = EnvConfig(kind="atari", max_noop_start=0, episodic_life=False)
    env = make_env(cfg, seed=0)
    env.reset()
    ball_visible = []
    for _ in range(20):
        obs, _, done, _ = env.step(0)
        newest = obs[..., -1].astype(np.int32)
        # ball gray level ~236 vs paddle ~117 vs bg ~13
        ball_visible.append((newest > 200).sum() > 0)
        if done:
            env.reset()
    assert all(ball_visible)


def test_atari_episodic_life():
    cfg = EnvConfig(kind="atari", max_noop_start=0, episodic_life=True)
    env = make_env(cfg, seed=0)
    env.reset()
    # run until first life loss
    for _ in range(2000):
        obs, r, done, info = env.step(0)
        if done:
            break
    assert done and info["terminal"] and info["lives"] == 4
    # pseudo-reset continues same raw episode (lives stay at 4)
    env.reset()
    _, _, _, info2 = env.step(0)
    assert info2["lives"] in (3, 4)


def test_grayscale_weights():
    frame = np.zeros((2, 2, 3), np.uint8)
    frame[..., 1] = 100
    np.testing.assert_allclose(grayscale(frame), 58.7)


def test_pendulum():
    env = PendulumSwingUp(seed=0)
    obs = env.reset()
    assert obs.shape == (3,)
    assert abs(float(np.hypot(obs[0], obs[1])) - 1.0) < 1e-5
    total = 0.0
    for _ in range(200):
        obs, r, done, info = env.step(np.array([0.5]))
        assert r <= 0.0
        total += r
    assert done and abs(info["episode_return"] - total) < 1e-6


def test_vector_env_autoreset():
    envs = SyncVectorEnv([CartPole(seed=i) for i in range(4)])
    obs = envs.reset()
    assert obs.shape == (4, 4)
    saw_done = False
    for t in range(600):
        obs, r, dones, infos = envs.step(np.ones(4, np.int32))
        assert obs.shape == (4, 4) and dones.shape == (4,)
        if dones.any():
            saw_done = True
            i = int(np.argmax(dones))
            assert "episode_return" in infos[i]
            break
    assert saw_done


def test_make_env_unknown_kind():
    with pytest.raises(ValueError):
        make_env(EnvConfig(kind="doom"), seed=0)


def test_dm_control_adapter_if_available():
    from ape_x_dqn_tpu.envs.control import HAVE_DM_CONTROL, make_control
    if not HAVE_DM_CONTROL:
        pytest.skip("dm_control not installed")
    from ape_x_dqn_tpu.configs import EnvConfig
    env = make_control(EnvConfig(id="cartpole_balance", kind="control"),
                       seed=0)
    obs = env.reset()
    assert obs.dtype == np.float32 and obs.shape == env.spec.obs_shape
    o, r, done, info = env.step(np.zeros(env.spec.action_dim, np.float32))
    assert o.shape == env.spec.obs_shape and "terminal" in info


def test_atari_truncation_full_resets_with_episodic_life():
    """Regression: time-limit truncation with episodic_life must force a
    full raw reset instead of pseudo-resetting forever."""
    cfg = EnvConfig(kind="atari", max_noop_start=0, episodic_life=True,
                    max_episode_frames=12)
    env = make_env(cfg, seed=0)
    env.reset()
    for _ in range(3):
        _, _, done, info = env.step(0)
    assert done and "episode_return" in info
    env.reset()
    # after the forced full reset the frame counter restarts
    _, _, done2, info2 = env.step(0)
    assert not done2


def test_vector_env_keeps_terminal_obs():
    envs = SyncVectorEnv([CartPole(seed=i) for i in range(2)])
    envs.reset()
    for _ in range(600):
        obs, r, dones, infos = envs.step(np.zeros(2, np.int32))
        if dones.any():
            i = int(np.argmax(dones))
            assert "terminal_obs" in infos[i]
            # reset obs differs from the terminal obs it replaced
            assert not np.array_equal(infos[i]["terminal_obs"], obs[i])
            break
    else:
        raise AssertionError("no episode ended")


def test_native_preproc_matches_numpy():
    """The fused C++ observation kernel (cpp/preproc.cpp) must be
    bit-identical to the numpy grayscale+bilinear_resize path, so the
    two are interchangeable mid-run (envs/atari.py _observe)."""
    from ape_x_dqn_tpu.envs import native
    from ape_x_dqn_tpu.envs.atari import bilinear_resize, grayscale

    if not native.available():
        pytest.skip("no g++ toolchain for the native kernel")
    rng = np.random.default_rng(0)
    for h, w, out in [(210, 160, 84), (64, 48, 84), (84, 84, 84),
                      (37, 91, 10)]:
        f0 = rng.integers(0, 256, (h, w, 3)).astype(np.uint8)
        f1 = rng.integers(0, 256, (h, w, 3)).astype(np.uint8)
        # pair (max-pooled) and single-frame calls
        for a, b in [(f0, f1), (f0, None)]:
            fm = a if b is None else np.maximum(a, b)
            ref = np.clip(bilinear_resize(grayscale(fm), out, out),
                          0, 255).astype(np.uint8)
            got = native.preproc(a, b, out, out)
            np.testing.assert_array_equal(got, ref,
                                          err_msg=f"{h}x{w}->{out}")


def test_explicit_dm_control_id_errors_without_dm_control(monkeypatch):
    """An underscore id explicitly names a dm_control task; with
    dm_control absent it must raise, not silently train the 3-d
    synthetic pendulum under the requested label."""
    from ape_x_dqn_tpu.envs import control

    monkeypatch.setattr(control, "HAVE_DM_CONTROL", False)
    with pytest.raises(ImportError, match="dm_control"):
        control.make_control(EnvConfig(id="humanoid_stand",
                                       kind="control"), seed=0)
    # the no-underscore native stand-in still works
    env = control.make_control(EnvConfig(id="pendulum", kind="control"),
                               seed=0)
    assert env.spec.obs_shape == (3,)


def test_vector_env_hands_rewards_back_unrounded():
    """An n-step return sums the envs' own doubles and rounds once, where
    it is shipped (runtime/actor.py): float32 here would round twice."""
    from ape_x_dqn_tpu.envs.base import Env

    class Third(Env):
        spec = CartPole(seed=0).spec

        def reset(self):
            return np.zeros(4, np.float32)

        def step(self, action):
            return np.zeros(4, np.float32), 1 / 3, False, {}

    _, rewards, _, _ = SyncVectorEnv([Third()]).step([0])
    assert rewards.dtype == np.float64 and rewards[0] == 1 / 3
