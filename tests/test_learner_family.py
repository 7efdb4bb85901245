"""The seam PR 28 cut: a family is a value (runtime/learner.py's
LearnerFamily, bound in runtime/family.py's table), the K-batch cycle
is defined once (SingleChipLearner) and inherited by the sharded
learner, and every driver builds its learner through
runtime/family.py::build_learner.

(a) build_learner from a tiny config trains on both stacks with both
    families and reports today's metric keys;
(b) the seven cycle endpoints are defined on SingleChipLearner and not
    again on DistLearner;
(c) source scans: each loss maker is called from one module, the
    Q-loss is differentiated in one place, the old classes are gone,
    and the three drivers construct no learner themselves.
"""

import inspect
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ape_x_dqn_tpu
from ape_x_dqn_tpu.configs import (
    LearnerConfig, NetworkConfig, ParallelConfig, ReplayConfig, RunConfig)
from ape_x_dqn_tpu.envs.base import EnvSpec
from ape_x_dqn_tpu.models import build_network
from ape_x_dqn_tpu.parallel.dist_learner import DistLearner
from ape_x_dqn_tpu.parallel.mesh import make_mesh
from ape_x_dqn_tpu.replay.prioritized import PrioritizedReplay
from ape_x_dqn_tpu.replay.sequence import sequence_item_spec
from ape_x_dqn_tpu.runtime.family import build_learner, learner_family
from ape_x_dqn_tpu.runtime.learner import (
    SingleChipLearner, transition_item_spec)

PKG = os.path.dirname(ape_x_dqn_tpu.__file__)
SPEC = EnvSpec(obs_shape=(4,), obs_dtype=np.dtype(np.float32),
               discrete=True, num_actions=2)
DP, N, T, LSTM = 2, 16, 6, 8

STEP_KEYS = {"loss", "q_mean", "td_abs_mean", "grad_norm", "diag"}
DIAG_SHARD_KEYS = {"shard_td_mean_min", "shard_td_mean_max"}


def _tiny(family: str, dist: bool):
    """-> (cfg, net, params, item_spec, items [N, ...], td_abs [N])."""
    rng = np.random.default_rng(0)
    lcfg = LearnerConfig(batch_size=8, n_step=2, sample_chunk=2,
                         target_sync_every=3, lr=1e-3)
    pcfg = ParallelConfig(dp=DP if dist else 1, tp=1)
    if family == "dqn":
        cfg = RunConfig(
            network=NetworkConfig(kind="mlp", mlp_hidden=(16,),
                                  compute_dtype="float32"),
            learner=lcfg, parallel=pcfg)
        net = build_network(cfg.network, SPEC)
        params = net.init(jax.random.key(0), jnp.zeros((1, 4)))
        item_spec = transition_item_spec(SPEC.obs_shape, jnp.float32)
        items = {
            "obs": rng.normal(size=(N, 4)).astype(np.float32),
            "action": rng.integers(0, 2, N).astype(np.int32),
            "reward": rng.normal(size=N).astype(np.float32),
            "next_obs": rng.normal(size=(N, 4)).astype(np.float32),
            "discount": np.full(N, 0.99, np.float32),
        }
    else:
        cfg = RunConfig(
            network=NetworkConfig(kind="lstm_q", lstm_size=LSTM,
                                  torso_dense=16,
                                  compute_dtype="float32"),
            replay=ReplayConfig(kind="sequence", seq_length=T, burn_in=2,
                                seq_overlap=3),
            learner=lcfg, parallel=pcfg)
        net = build_network(cfg.network, SPEC)
        z = jnp.zeros((1, LSTM), jnp.float32)
        params = net.init(jax.random.key(0), jnp.zeros((1, T, 4)), (z, z))
        item_spec = sequence_item_spec(SPEC.obs_shape, np.float32, T, LSTM)
        items = {
            "obs": rng.normal(size=(N, T, 4)).astype(np.float32),
            "actions": rng.integers(0, 2, (N, T)).astype(np.int32),
            "rewards": rng.normal(size=(N, T)).astype(np.float32),
            "terminals": np.zeros((N, T), np.float32),
            "mask": np.ones((N, T), np.float32),
            "init_c": np.zeros((N, LSTM), np.float32),
            "init_h": np.zeros((N, LSTM), np.float32),
        }
    td = (rng.random(N) + 0.1).astype(np.float32)
    return cfg, net, params, item_spec, items, td


@pytest.mark.parametrize("dist", [False, True], ids=["single", "dist_dp2"])
@pytest.mark.parametrize("family", ["dqn", "r2d2"])
def test_build_learner_trains_with_todays_metric_keys(family, dist):
    cfg, net, params, item_spec, items, td = _tiny(family, dist)
    replay = PrioritizedReplay(capacity=32)
    if dist:
        learner = build_learner(cfg, net, replay, make_mesh(dp=DP, tp=1))
        assert type(learner) is DistLearner
        state = learner.init(params, item_spec, jax.random.key(1))
        state = learner.add(
            state,
            jax.tree.map(lambda x: x.reshape(DP, N // DP, *x.shape[1:]),
                         items),
            td.reshape(DP, N // DP))
    else:
        learner = build_learner(cfg, net, replay)
        assert type(learner) is SingleChipLearner
        state = learner.init(params, replay.init(item_spec),
                             jax.random.key(1))
        state = learner.add(state, items, td)
    assert learner.family.name == family
    # 5 grad steps at K=2: one exact single first, then two macro-steps
    state, m = learner.train_many(state, 5)
    assert int(state.step) == 5
    assert np.isfinite(float(m["loss"]))
    want = STEP_KEYS | ({"valid_frac"} if family == "r2d2" else set())
    assert set(m) == want
    assert DIAG_SHARD_KEYS <= set(m["diag"]) if dist \
        else not (DIAG_SHARD_KEYS & set(m["diag"]))
    # the attribute names the net's signature
    assert hasattr(learner, "net_apply_seq") == (family == "r2d2")
    assert hasattr(learner, "net_apply") == (family == "dqn")


DECODER_KEYS = {
    # a net WITH an expert layer reports it; a net without one reports
    # its loop, and nothing of an expert layer
    "glm_tiny_q": {"valid_frac", "moe_rows", "moe_rows_grad",
                   "moe_load_max_over_mean", "moe_compact_share"},
    "ouro_tiny_q": {"valid_frac", "loop_block_applications",
                    "loop_exit_mass_last"},
    # a routed net WITH A SCAN LAYER adds the scan's two counters
    "kimi_linear_tiny_q": {"valid_frac", "moe_rows", "moe_rows_grad",
                           "moe_load_max_over_mean", "moe_compact_share",
                           "kda_chunks", "kda_state_rms_last"},
    # a routed net THAT OFFERS THE HEAD'S COLUMN READ adds its counter
    "trinity_tiny_q": {"valid_frac", "moe_rows", "moe_rows_grad",
                       "moe_load_max_over_mean", "moe_compact_share",
                       "head_columns"}}
ROUTED = ("glm_tiny_q", "kimi_linear_tiny_q", "trinity_tiny_q")


@pytest.mark.parametrize("preset", list(DECODER_KEYS))
def test_decoder_q_family_reads_expert_statistics_only_from_a_net_with_them(
        preset):
    """`decoder_q_family` over a net with and without an expert layer:
    the metric keys, no `moe_*` from the looped net, and the aux that
    the benchmark's check differentiates for (`q`; the selections only
    where there is one)."""
    from ape_x_dqn_tpu.configs import get_config

    cfg = get_config(preset)
    net = build_network(cfg.network, None)
    assert hasattr(net, "share") == (preset in ROUTED)
    family = learner_family(cfg, net)
    assert family.name == "decoder_q"
    assert set(family.metric_keys) == DECODER_KEYS[preset]
    length, n = cfg.replay.seq_length, cfg.learner.batch_size
    rng = np.random.default_rng(0)
    items = {
        "obs": rng.integers(0, net.num_actions, (N, length)).astype(np.int32),
        "actions": rng.integers(0, net.num_actions,
                                (N, length)).astype(np.int32),
        "rewards": rng.normal(size=(N, length)).astype(np.float32),
        "terminals": np.zeros((N, length), np.float32),
        "mask": np.ones((N, length), np.float32)}
    params = net.init(jax.random.key(0))
    _, aux = jax.jit(family.loss_fn)(
        params, params, family.make_batch(
            {k: v[:n] for k, v in items.items()}), jnp.ones(n))
    assert set(family.metric_keys) <= set(aux) and "q" in aux
    assert ("topk_online" in aux) == (preset in ROUTED)
    replay = PrioritizedReplay(capacity=32)
    learner = build_learner(cfg, net, replay)
    assert type(learner) is SingleChipLearner
    state = learner.init(
        params, replay.init(sequence_item_spec((), np.int32, length, {})),
        jax.random.key(1))
    state = learner.add(state, items, np.ones(N, np.float32))
    state, m = learner.train_many(state, 2)
    assert set(m) == STEP_KEYS | DECODER_KEYS[preset]
    assert any(k.startswith("moe_") for k in m) == (preset in ROUTED)
    assert any(k.startswith("kda_") for k in m) == (
        preset == "kimi_linear_tiny_q")
    assert np.isfinite(float(m["loss"]))


def test_learner_family_is_an_immutable_value():
    cfg, net, *_ = _tiny("dqn", False)
    fam = learner_family(cfg, net)
    with pytest.raises(AttributeError):
        fam.loss_fn = None


CYCLE = ["train_step", "train_step_k", "sample_k", "learn_k", "train_many",
         "_train_many_prefetch", "_train_step_k"]


@pytest.mark.parametrize("name", CYCLE)
def test_cycle_endpoint_is_defined_once_and_inherited(name):
    assert name in vars(SingleChipLearner)
    assert name not in vars(DistLearner)
    assert getattr(DistLearner, name) is getattr(SingleChipLearner, name)


def _package_sources():
    for root, _, files in os.walk(PKG):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(root, f)
                with open(path) as fh:
                    yield os.path.relpath(path, PKG), fh.read()


@pytest.mark.parametrize("maker", ["make_dqn_loss", "make_r2d2_loss"])
def test_each_loss_is_bound_in_one_module(maker):
    call = re.compile(r"(?<!def )\b" + maker + r"\(")
    callers = [p for p, src in _package_sources() if call.search(src)]
    assert callers == [os.path.join("runtime", "family.py")]


def test_the_q_loss_is_differentiated_in_one_place():
    sites = [(p, src.count("value_and_grad(")) for p, src in
             _package_sources() if "value_and_grad(" in src
             and p != os.path.join("runtime", "dpg_learner.py")]
    assert sites == [(os.path.join("runtime", "learner.py"), 1)]


@pytest.mark.parametrize("gone", ["DQNLearner", "SequenceLearner",
                                  "DistDQNLearner", "DistSequenceLearner"])
def test_family_subclasses_are_gone(gone):
    defs = re.compile(r"^\s*(class\s+" + gone + r"\b|" + gone + r"\s*=)",
                      re.M)
    assert [p for p, src in _package_sources() if defs.search(src)] == []
    assert not os.path.exists(
        os.path.join(PKG, "runtime", "sequence_learner.py"))


@pytest.mark.parametrize("module", ["driver", "multihost_driver",
                                    "single_process"])
def test_drivers_build_their_learner_in_family_py(module):
    import importlib

    src = inspect.getsource(
        importlib.import_module(f"ape_x_dqn_tpu.runtime.{module}"))
    assert "build_learner(" in src
    assert not re.search(r"\b(SingleChipLearner|DistLearner|DPGLearner)\(",
                         src)
