"""Learning-health plane (ISSUE 10, obs/learning.py): in-graph
diagnostics on all four learner cycles, per-tenant gauge publication
through a real catch run, the dp-sharded per-shard closure, and the
warn-only LearnMonitor anomaly engine."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.configs import (
    EnvConfig, LearnerConfig, NetworkConfig, ObsConfig, ReplayConfig,
    get_config)
from ape_x_dqn_tpu.envs.base import EnvSpec
from ape_x_dqn_tpu.models import build_network
from ape_x_dqn_tpu.obs.core import NULL_OBS, build_obs
from ape_x_dqn_tpu.obs.learning import LearnMonitor
from ape_x_dqn_tpu.replay.prioritized import PrioritizedReplay
from ape_x_dqn_tpu.runtime.family import dqn_family, r2d2_family
from ape_x_dqn_tpu.runtime.learner import (
    SingleChipLearner, transition_item_spec)
from ape_x_dqn_tpu.utils.metrics import Metrics
from ape_x_dqn_tpu.utils.rng import component_key

VEC_SPEC = EnvSpec(obs_shape=(4,), obs_dtype=np.dtype(np.float32),
                   discrete=True, num_actions=2)

# every key sgd_diag + replay_health put on the single-chip diag pytree
DIAG_KEYS = {
    "td_abs_p50", "td_abs_p90", "td_abs_p99", "td_signed_mean",
    "q_mean", "q_max", "target_q_mean", "q_gap", "grad_norm",
    "update_ratio", "is_ess_frac", "sample_age_p50", "sample_age_p90",
    "prio_staleness_frac", "priority_top_frac",
}


def _flat_items(rng, n):
    return {
        "obs": jnp.asarray(rng.standard_normal((n, 4)), jnp.float32),
        "action": jnp.asarray(rng.integers(0, 2, n), jnp.int32),
        "reward": jnp.asarray(rng.standard_normal(n), jnp.float32),
        "next_obs": jnp.asarray(rng.standard_normal((n, 4)),
                                jnp.float32),
        "discount": jnp.full((n,), 0.97, jnp.float32),
    }


def _assert_diag(diag, extra=()):
    assert set(DIAG_KEYS) | set(extra) == set(diag), sorted(diag)
    for k, v in diag.items():
        v = float(v)
        assert np.isfinite(v), (k, v)
    assert 0.0 < float(diag["is_ess_frac"]) <= 1.0 + 1e-6
    assert float(diag["td_abs_p50"]) <= float(diag["td_abs_p90"]) \
        <= float(diag["td_abs_p99"])
    assert 0.0 <= float(diag["priority_top_frac"]) <= 1.0 + 1e-6


# -- in-graph diagnostics on each learner cycle ---------------------------

def test_dqn_learner_diag_finite():
    net = build_network(NetworkConfig(kind="mlp", mlp_hidden=(32,)),
                        VEC_SPEC)
    params = net.init(component_key(3, "net"),
                      np.zeros((1, 4), np.float32))
    lcfg = LearnerConfig(batch_size=32)
    learner = SingleChipLearner(dqn_family(net.apply, lcfg),
                                PrioritizedReplay(capacity=512), lcfg)
    state = learner.init(
        params, learner.replay.init(
            transition_item_spec(VEC_SPEC.obs_shape,
                                 VEC_SPEC.obs_dtype)),
        component_key(3, "learner"))
    rng = np.random.default_rng(7)
    state = learner.add(state, _flat_items(rng, 256), jnp.ones(256))
    state, m = learner.train_step(state)
    assert "diag" in m
    _assert_diag(m["diag"])
    # fused path: draw and write-back see the same tree
    assert float(m["diag"]["prio_staleness_frac"]) == 0.0
    # the diag pytree rides the train_many scan (last-step fold)
    state, m = learner.train_many(state, 3)
    _assert_diag(m["diag"])


def test_sequence_learner_diag_finite():
    from ape_x_dqn_tpu.models import ApeXLSTMQNet
    from ape_x_dqn_tpu.replay.sequence import sequence_item_spec

    net = ApeXLSTMQNet(num_actions=2, lstm_size=8, dense=16,
                       compute_dtype="float32", mlp_torso=True)
    z = jnp.zeros((1, 8), jnp.float32)
    params = net.init(jax.random.key(0),
                      jnp.zeros((1, 4, 2), jnp.float32), (z, z))
    replay = PrioritizedReplay(capacity=64)
    spec = sequence_item_spec((2,), np.float32, 4, 8)
    lcfg = LearnerConfig(batch_size=8, n_step=2, value_rescale=True,
                         target_sync_every=10, lr=1e-3)
    rcfg = ReplayConfig(seq_length=4, burn_in=1)
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
    state, m = learner.train_step(state)
    _assert_diag(m["diag"])


def test_dpg_learner_diag_finite():
    from ape_x_dqn_tpu.models import DPGActor, DPGCritic
    from ape_x_dqn_tpu.runtime.dpg_learner import (
        DPGLearner, continuous_item_spec)

    actor = DPGActor(action_dim=1, action_low=-2, action_high=2,
                     hidden=(16, 16))
    critic = DPGCritic(hidden=(16, 16))
    obs0 = jnp.zeros((1, 3), jnp.float32)
    a0 = jnp.zeros((1, 1), jnp.float32)
    actor_params = actor.init(jax.random.key(0), obs0)
    critic_params = critic.init(jax.random.key(1), obs0, a0)
    replay = PrioritizedReplay(capacity=256)
    spec = continuous_item_spec((3,), np.float32, 1)
    lcfg = LearnerConfig(batch_size=32, n_step=5, critic_lr=1e-3,
                         policy_lr=1e-4, tau=0.05)
    learner = DPGLearner(actor.apply, critic.apply, replay, lcfg)
    state = learner.init(actor_params, critic_params, replay.init(spec),
                         jax.random.key(2))
    rng = np.random.default_rng(0)
    items = {
        "obs": jnp.asarray(rng.normal(size=(64, 3)), jnp.float32),
        "action": jnp.asarray(rng.uniform(-2, 2, (64, 1)), jnp.float32),
        "reward": jnp.asarray(rng.normal(size=(64,)), jnp.float32),
        "next_obs": jnp.asarray(rng.normal(size=(64, 3)), jnp.float32),
        "discount": jnp.full((64,), 0.95, jnp.float32),
    }
    state = learner.add(state, items, jnp.ones(64))
    state, m = learner.train_step(state)
    _assert_diag(m["diag"])


def test_dist_learner_diag_shard_closure():
    """dp=2 dist learner: diag scalars are finite and the per-shard
    mean-|TD| envelope closes over the global mean (the min/max are the
    psum'd extremes of exactly the per-shard means the global averages,
    so min <= global <= max is an identity, not a tolerance)."""
    from ape_x_dqn_tpu.parallel.dist_learner import DistLearner
    from ape_x_dqn_tpu.parallel.mesh import make_mesh

    dp = 2
    mesh = make_mesh(dp=dp, tp=1)
    net = build_network(
        NetworkConfig(kind="mlp", mlp_hidden=(64,), dueling=False,
                      compute_dtype="float32"), VEC_SPEC)
    params = net.init(jax.random.key(0), jnp.zeros((1, 4)))
    lcfg = LearnerConfig(batch_size=32, target_sync_every=10)
    learner = DistLearner(
        dqn_family(net.apply, lcfg),
        PrioritizedReplay(capacity=64, alpha=0.6, beta=0.4), lcfg, mesh)
    state = learner.init(params,
                         transition_item_spec((4,), jnp.float32),
                         jax.random.key(1))
    rng = np.random.default_rng(0)
    n = 16
    items = {
        "obs": jnp.asarray(rng.normal(size=(dp, n, 4)), jnp.float32),
        "action": jnp.asarray(rng.integers(0, 2, (dp, n)), jnp.int32),
        "reward": jnp.asarray(rng.normal(size=(dp, n)), jnp.float32),
        "next_obs": jnp.asarray(rng.normal(size=(dp, n, 4)),
                                jnp.float32),
        "discount": jnp.full((dp, n), 0.99, jnp.float32),
    }
    state = learner.add(state, items, jnp.ones((dp, n)))
    state, m = learner.train_step(state)
    diag = m["diag"]
    _assert_diag(diag, extra=("shard_td_mean_min", "shard_td_mean_max"))
    lo, hi = float(diag["shard_td_mean_min"]), \
        float(diag["shard_td_mean_max"])
    g = float(m["td_abs_mean"])
    assert lo <= g + 1e-6 and g <= hi + 1e-6, (lo, g, hi)


# -- end-to-end: catch run publishes the plane ----------------------------

@pytest.mark.parametrize("game", ["catch", "pong"])
def test_single_process_publishes_learn_gauges(tmp_path, game):
    """Tier-1 acceptance (ISSUE 10), one env family = one tenant: a
    short synthetic-Atari run with obs ON publishes finite,
    in-healthy-range learn_* gauges plus the tenant-prefixed
    duplicates, a clean learner fires zero degradation events, and the
    report's --check rows find nothing to flag."""
    from ape_x_dqn_tpu.obs.report import check_violations, summarize
    from ape_x_dqn_tpu.runtime.single_process import train_single_process

    jsonl = str(tmp_path / "run.jsonl")
    cfg = get_config("pong").replace(
        env=EnvConfig(id=game, kind="synthetic_atari"),
        network=NetworkConfig(kind="nature_cnn", dueling=True,
                              compute_dtype="float32"),
        replay=ReplayConfig(kind="prioritized", capacity=2048,
                            min_fill=300),
        learner=LearnerConfig(batch_size=16, n_step=3,
                              target_sync_every=16, sample_chunk=2),
        obs=ObsConfig(enabled=True, publish_every_steps=50,
                      heartbeat_timeout_s=120.0),
    )
    metrics = Metrics(log_path=jsonl)
    out = train_single_process(cfg, total_env_frames=420,
                               metrics=metrics, train_every=2)
    metrics.close()
    assert out["grad_steps"] > 0
    recs = [json.loads(ln) for ln in open(jsonl)]
    gauges = {}
    for r in recs:
        gauges.update({k: v for k, v in r.items()
                       if k.startswith("gauge/learn")})
    for key in DIAG_KEYS:
        v = gauges.get(f"gauge/learn_{key}")
        assert v is not None, f"learn_{key} never published"
        assert np.isfinite(v), (key, v)
        # tenant duplicate under the env-family prefix
        assert gauges.get(f"gauge/learn/{game}/{key}") == v, key
    # a healthy learner sits inside every monitor bound
    assert abs(gauges["gauge/learn_q_max"]) < 1e3
    assert gauges["gauge/learn_is_ess_frac"] > 0.05
    assert gauges["gauge/learn_update_ratio"] > 1e-9
    assert gauges["gauge/learn_priority_top_frac"] < 0.5
    assert not any("learning_degradation" in r for r in recs)
    # the report regroups the tenant keys and collects no events
    summary = summarize(recs)
    assert game in summary["tenants"]
    assert summary["tenants"][game]["q_mean"] == \
        gauges["gauge/learn_q_mean"]
    assert summary["learn_events"] == []
    assert check_violations(summary) == []


# -- the anomaly engine ---------------------------------------------------

class _FakeObs:
    def __init__(self):
        self.counts = []

    def count(self, name, n=1):
        self.counts.append(name)


class _FakeMetrics:
    def __init__(self):
        self.records = []

    def log(self, step, **kw):
        self.records.append({"step": step, **kw})


def test_learn_monitor_loss_spike_once_per_cooldown():
    obs, metrics = _FakeObs(), _FakeMetrics()
    mon = LearnMonitor(obs, metrics, spike_mult=10.0, alpha=0.2,
                       min_samples=3, cooldown_s=3600.0)
    for _ in range(3):
        mon.observe({}, 1.0, step=1, tenant="pong")
    assert metrics.records == []  # baseline warm-up never fires
    # injected spike: two consecutive spikes, one cooldown window ->
    # exactly one attributed event + one counter bump
    mon.observe({}, 100.0, step=2, tenant="pong")
    mon.observe({}, 100.0, step=3, tenant="pong")
    assert obs.counts == ["learning_degradations"]
    assert len(metrics.records) == 1
    ev = metrics.records[0]
    assert ev["learning_degradation"] == "loss_spike"
    assert ev["learn_tenant"] == "pong"
    assert ev["learn_value"] == pytest.approx(100.0)
    assert 0.0 < ev["learn_baseline"] < 10.0


def test_learn_monitor_q_blowup_attributed():
    obs, metrics = _FakeObs(), _FakeMetrics()
    mon = LearnMonitor(obs, metrics, cooldown_s=3600.0)
    mon.observe({"q_max": 5e3, "is_ess_frac": 0.9,
                 "update_ratio": 1e-3, "priority_top_frac": 0.01},
                0.5, step=7, tenant="breakout")
    assert len(metrics.records) == 1
    ev = metrics.records[0]
    assert ev["learning_degradation"] == "q_blowup"
    assert ev["learn_tenant"] == "breakout"
    assert ev["step"] == 7
    # cooldowns are per (tenant, rule): another tenant still fires
    mon.observe({"q_max": -5e3}, 0.5, step=8, tenant="pong")
    assert [r["learn_tenant"] for r in metrics.records] == \
        ["breakout", "pong"]


def test_learn_monitor_absolute_rules():
    obs, metrics = _FakeObs(), _FakeMetrics()
    mon = LearnMonitor(obs, metrics, cooldown_s=3600.0)
    mon.observe({"is_ess_frac": 0.01}, 0.5, tenant="a")
    mon.observe({"update_ratio": 0.0}, 0.5, tenant="b")
    mon.observe({"priority_top_frac": 0.9}, 0.5, tenant="c")
    rules = [r["learning_degradation"] for r in metrics.records]
    assert rules == ["ess_collapse", "dead_gradients",
                     "priority_collapse"]
    # NaN diagnostics never fire (and never poison the EWMA)
    mon.observe({"q_max": float("nan")}, float("nan"), tenant="d")
    assert len(metrics.records) == 3


# -- disabled obs emits nothing -------------------------------------------

def test_disabled_obs_learn_health_is_noop(tmp_path):
    jsonl = str(tmp_path / "off.jsonl")
    metrics = Metrics(log_path=jsonl)
    obs = build_obs(ObsConfig(enabled=False), metrics)
    assert obs is NULL_OBS
    assert obs.learn is None
    obs.learn_health({"q_max": 5e3}, 100.0, step=1, tenant="pong")
    metrics.close()
    recs = [json.loads(ln) for ln in open(jsonl)]
    assert not any(k.startswith(("gauge/learn", "hist/learn", "ctr/"))
                   for r in recs for k in r)


def test_obs_learn_health_toggle_off(tmp_path):
    """ObsConfig(learn_health=False): the gauges still publish (they
    are cheap host reads) but no monitor exists, so injected anomalies
    produce no degradation events."""
    jsonl = str(tmp_path / "toggle.jsonl")
    metrics = Metrics(log_path=jsonl)
    obs = build_obs(ObsConfig(enabled=True, learn_health=False,
                              heartbeat_timeout_s=0.0), metrics)
    assert obs.learn is None
    obs.learn_health({"q_max": 5e3}, 100.0, step=1, tenant="pong")
    obs.publish(1)
    obs.close(1)
    metrics.close()
    recs = [json.loads(ln) for ln in open(jsonl)]
    assert any("gauge/learn_q_max" in r for r in recs)
    assert not any("learning_degradation" in r for r in recs)
