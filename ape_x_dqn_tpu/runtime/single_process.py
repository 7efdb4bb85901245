"""Single-process training driver — the reference's CPU smoke path.

Config 1 (SURVEY.md §3.5): one env, one net, device-resident replay, and
the single-jit learner, all in one process with no transport. This is
both the minimum end-to-end slice and the correctness oracle (CartPole
must reach >= 475 average return).

Works for any flat-transition discrete config (CartPole MLP, synthetic
Atari CNN) — the distributed runtime (runtime/driver.py) reuses the same
learner and replay, swapping the in-process env loop for actor processes.
"""

from __future__ import annotations

import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from ape_x_dqn_tpu.configs import RunConfig
from ape_x_dqn_tpu.envs import make_env
from ape_x_dqn_tpu.models import build_network
from ape_x_dqn_tpu.obs.core import build_obs
from ape_x_dqn_tpu.ops.nstep import NStepBuilder
from ape_x_dqn_tpu.replay.prioritized import (
    PrioritizedReplay, UniformReplayDevice)
from ape_x_dqn_tpu.runtime.family import build_learner
from ape_x_dqn_tpu.runtime.learner import transition_item_spec
from ape_x_dqn_tpu.utils.metrics import Metrics, log_run_header
from ape_x_dqn_tpu.utils.misc import next_pow2
from ape_x_dqn_tpu.utils.rng import RngStream, component_key


def build_replay(rcfg):
    cap = next_pow2(rcfg.capacity)
    if rcfg.kind == "uniform":
        return UniformReplayDevice(capacity=cap)
    return PrioritizedReplay(capacity=cap, alpha=rcfg.alpha, beta=rcfg.beta,
                             eps=rcfg.eps)


def train_single_process(cfg: RunConfig, total_env_frames: int | None = None,
                         metrics: Metrics | None = None,
                         solve_return: float | None = None,
                         train_every: int = 1,
                         flush_every: int = 32) -> dict:
    """Run config-1-style training; returns summary stats."""
    total = total_env_frames or cfg.total_env_frames
    metrics = metrics or Metrics()
    log_run_header(metrics, cfg)
    # `obs` is this loop's env observation; the observability facade
    # rides as `obs_` (NULL_OBS when cfg.obs is absent/disabled)
    obs_ = build_obs(getattr(cfg, "obs", None), metrics)
    # crash hooks (obs/blackbox.py): uninstalled again by obs_.close(),
    # so a healthy run leaves no dump behind
    obs_.blackbox.install()
    obs_.register("actor-0")
    obs_.register("learner")
    env = make_env(cfg.env, seed=cfg.seed)
    net = build_network(cfg.network, env.spec)

    obs = env.reset()
    params = net.init(component_key(cfg.seed, "net_init"), obs[None])
    fwd = jax.jit(net.apply)

    replay = build_replay(cfg.replay)
    # host mirror of the ring's skip-to-head write cursor: maps sampled
    # slot indices back to the grad-step they were written at (None
    # when obs is disabled)
    age_tracker = obs_.age_tracker(next_pow2(cfg.replay.capacity))
    item_spec = transition_item_spec(env.spec.obs_shape,
                                     env.spec.obs_dtype)
    learner = build_learner(cfg, net, replay)
    state = learner.init(params, replay.init(item_spec),
                         component_key(cfg.seed, "learner"))

    nstep = NStepBuilder(cfg.learner.n_step, cfg.learner.gamma)
    actor_rng = np.random.default_rng(
        RngStream(cfg.seed, "actor_host").next_uint32())

    pending: list = []
    returns: deque[float] = deque(maxlen=100)
    losses: deque[float] = deque(maxlen=100)
    frames = 0
    grad_steps = 0
    # K-batch relaxation: bank K training opportunities, then one
    # train_many(K) macro-dispatch — same grad-steps-per-frame as the
    # exact path, routed through _train_step_k (learning-parity e2e:
    # tests/test_e2e_catch.py::test_cnn_learns_catch_kbatch)
    sample_chunk = max(getattr(cfg.learner, "sample_chunk", 1), 1)
    train_bank = 0
    # Double-buffered sampling (LearnerConfig.sample_prefetch): the
    # host keeps ONE macro-step's sample in flight — each macro
    # opportunity first dispatches sample_k against the CURRENT tree,
    # then learn_k on the sample drawn at the PREVIOUS opportunity, so
    # the descent/gather dispatch can overlap the previous dispatch's
    # SGD work on device. The pending sample's priorities (and, after
    # interleaved adds, even its slots) may be one round stale — the
    # async-replay semantics the reference's host-side sampler always
    # has, parity-tested on the catch e2e
    # (tests/test_e2e_catch.py::test_cnn_learns_catch_prefetch).
    sample_prefetch = (sample_chunk > 1
                       and getattr(cfg.learner, "sample_prefetch", False))
    pending_sample = None
    eps_final = 0.05
    eps_decay_frames = max(total // 10, 1_000)

    def flush():
        nonlocal pending, state
        if not pending:
            return
        with obs_.span("replay.add", n=len(pending)):
            items = {
                "obs": jnp.asarray(np.stack([t.obs for t in pending])),
                "action": jnp.asarray([t.action for t in pending],
                                      jnp.int32),
                "reward": jnp.asarray([t.reward for t in pending],
                                      jnp.float32),
                "next_obs": jnp.asarray(
                    np.stack([t.next_obs for t in pending])),
                "discount": jnp.asarray([t.discount for t in pending],
                                        jnp.float32),
            }
            state = learner.add(state, items, jnp.ones(len(pending)))
        if age_tracker is not None:
            age_tracker.on_add(len(pending), grad_steps)
        obs_.count("replay_adds", len(pending))
        pending = []

    def traced_train(k: int):
        """Observed macro-step: the split sample_k/learn_k dispatch
        (parity-tested against train_step/_k in PR 1) so the tracer
        sees replay.sample and learner.learn as real host spans —
        block_until_ready inside each span keeps the timing honest
        against jax's async dispatch. Priority write-back and target
        sync run inside the learn jit: a `jax.profiler` trace shows them
        on the device plane as `cycle.write_back` / `cycle.target_sync`
        (runtime/learner.py::CYCLE_SCOPES)."""
        nonlocal state
        # roofline attribution (obs/profiling.py): AOT lower/compile of
        # the exact dispatch signature captures cost_analysis FLOP/byte
        # roofs AND populates the jit call cache, so the timed call
        # below compiles nothing extra. First observed macro-step only.
        if not obs_.stage_attached("sample_k"):
            obs_.stage_attach(
                "sample_k", k, compile_fn=lambda: type(learner).sample_k
                .lower(learner, state, k).compile())
        with obs_.stage_window("sample_k", k):
            with obs_.span("replay.sample", k=k):
                sample, rng2 = learner.sample_k(state, k)
                jax.block_until_ready(sample)
        if age_tracker is not None:
            obs_.observe_sample_ages(
                age_tracker.ages(np.asarray(sample[1]), grad_steps))
        if not obs_.stage_attached("learn_k"):
            obs_.stage_attach(
                "learn_k", k, compile_fn=lambda: type(learner).learn_k
                .lower(learner, state._replace(rng=rng2), sample, k)
                .compile())
        with obs_.stage_window("learn_k", k):
            with obs_.span("learner.learn", k=k):
                state, m = learner.learn_k(state._replace(rng=rng2),
                                           sample, k)
                m = jax.block_until_ready(m)
        obs_.observe("td_abs", float(m["td_abs_mean"]))
        # the acting policy reads state.params directly — lag is truly 0
        obs_.observe("param_lag_steps", 0)
        return m

    pub_every = max(getattr(getattr(cfg, "obs", None),
                            "publish_every_steps", 500) or 500, 1)
    # publish-boundary rate window for the perf-regression engine
    rate_t = time.monotonic()
    rate_frames = 0
    rate_steps = 0
    while frames < total:
        obs_.beat("actor-0", f"frame {frames}")
        eps = max(eps_final, 1.0 - (1.0 - eps_final) * frames
                  / eps_decay_frames)
        with obs_.span("actor.step"):
            if actor_rng.random() < eps:
                action = int(actor_rng.integers(env.spec.num_actions))
            else:
                with obs_.span("actor.inference"):
                    q = fwd(state.params, obs[None])
                action = int(jnp.argmax(q[0]))
            next_obs, reward, done, info = env.step(action)
        frames += 1
        truncated = done and not info.get("terminal", done)
        pending.extend(nstep.append(obs, action, reward, next_obs,
                                    info.get("terminal", done), truncated))
        obs = env.reset() if done else next_obs
        if done and "episode_return" in info:
            returns.append(info["episode_return"])

        if len(pending) >= flush_every:
            flush()

        if (int(state.replay.size) + len(pending) >= cfg.replay.min_fill
                and frames % train_every == 0):
            flush()
            prev_grad_steps = grad_steps
            m = None
            if sample_chunk > 1:
                # bank K training opportunities, then one K-batch
                # macro-dispatch (<=K-1 banked opportunities evaporate
                # at loop end — same grad/frame ratio, harmless)
                train_bank += 1
                if train_bank >= sample_chunk:
                    train_bank = 0
                    if obs_.enabled:
                        # observed runs take the split dispatch so the
                        # sample/learn stages are separately timeable;
                        # the prefetch overlap is deliberately broken
                        # here — honest stage timing needs the sync
                        m = traced_train(sample_chunk)
                    elif sample_prefetch:
                        if pending_sample is None:  # pipeline prologue
                            pending_sample, rng2 = learner.sample_k(
                                state, sample_chunk)
                            state = state._replace(rng=rng2)
                        nxt, rng2 = learner.sample_k(state, sample_chunk)
                        state, m = learner.learn_k(
                            state._replace(rng=rng2), pending_sample,
                            sample_chunk)
                        pending_sample = nxt
                    else:
                        state, m = learner.train_step_k(state,
                                                        sample_chunk)
                    grad_steps += sample_chunk
            else:
                if obs_.enabled:
                    m = traced_train(1)
                else:
                    state, m = learner.train_step(state)
                grad_steps += 1
            if m is not None:
                obs_.beat("learner", f"grad_step {grad_steps}")
                obs_.maybe_profile(grad_steps)
                losses.append(float(m["loss"]))
                # boundary CROSSING, not equality: K-sized increments
                # would otherwise only hit exact multiples at lcm(K, 500)
                if prev_grad_steps // 500 != grad_steps // 500:
                    metrics.log(grad_steps, frames=frames,
                                loss=float(m["loss"]),
                                q_mean=float(m["q_mean"]),
                                avg_return=(float(np.mean(returns))
                                            if returns else 0.0),
                                eps=eps)
                if prev_grad_steps // pub_every != \
                        grad_steps // pub_every:
                    obs_.gauge("replay_occupancy",
                               int(state.replay.size))
                    if obs_.enabled and "diag" in m:
                        # learning-health plane: observed runs go
                        # through traced_train, which already
                        # block_until_ready'd m — no extra sync here
                        obs_.learn_health(
                            m["diag"], float(m["loss"]),
                            step=grad_steps, tenant=cfg.env.id)
                    now = time.monotonic()
                    if now > rate_t:
                        dt = now - rate_t
                        obs_.perf_rate("grad_steps_per_s",
                                       (grad_steps - rate_steps) / dt,
                                       step=grad_steps)
                        obs_.perf_rate("env_fps",
                                       (frames - rate_frames) / dt,
                                       step=grad_steps)
                    rate_t, rate_frames, rate_steps = \
                        now, frames, grad_steps
                    obs_.publish(grad_steps)
        obs_.check_stalled()
        if (solve_return is not None and len(returns) >= 20
                and np.mean(list(returns)[-20:]) >= solve_return):
            break

    # final snapshot + trace flush (the stall path closes inside
    # check_stalled before raising, so both exits produce artifacts)
    obs_.gauge("replay_occupancy", int(state.replay.size))
    obs_.close(grad_steps)
    return {
        "frames": frames,
        "grad_steps": grad_steps,
        "avg_return": float(np.mean(returns)) if returns else 0.0,
        "last20_return": (float(np.mean(list(returns)[-20:]))
                          if len(returns) >= 1 else 0.0),
        "episodes": len(returns),
        "final_loss": float(np.mean(losses)) if losses else float("nan"),
    }
