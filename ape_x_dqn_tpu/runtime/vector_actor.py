"""Vectorized actor: one thread drives K envs per batched inference query.

The scalar actor (runtime/actor.py) makes one single-observation RPC per
env step, so its throughput is bounded by RPC round-trips — the round-2
live soak measured the whole driver actor-bound at ~10-15 env-fps
(PERF.md "Live driver vs bench"). The reference keeps ~50k aggregate
env-fps with per-actor GPUs (SURVEY.md §6); the TPU-native answer is the
batched inference server (SURVEY.md §2.3 item 4) — which only pays off
when queries arrive in bulk. This module closes that loop: one actor
thread steps a SyncVectorEnv of K envs and ships ONE K-item query per
vector step (`BatchedInferenceServer.query_batch`), so the server sees
batch-K work from a single thread and the per-step RPC cost amortizes
K ways (SURVEY.md §2.4 "inference batching parallelism", §7 hard part 3).

Per-env bookkeeping (n-step building, initial-priority resolution,
frame-segment assembly) stays host-side numpy per env core — it is cheap
relative to the RPC+forward that the batching removes. The one-step
pending mechanism is the scalar actor's, applied per env: a transition
emitted at step t needs max_a Q(s_{t+n}), which is exactly env j's slice
of the NEXT vector query; truncation flushes batch their terminal
observations into one extra query per vector step.

Each env core owns a distinct slot of the global Horgan eps schedule:
vector actor i's env j is global slot i*K+j of num_actors*K, so a fleet
of vector actors spans the same exploration diversity as num_actors*K
scalar actors.
"""

from __future__ import annotations

import threading
from typing import Callable

import jax
import numpy as np

from ape_x_dqn_tpu.configs import RunConfig
from ape_x_dqn_tpu.envs import make_env
from ape_x_dqn_tpu.envs.vector import SyncVectorEnv
from ape_x_dqn_tpu.obs.core import NULL_OBS
from ape_x_dqn_tpu.ops.nstep import NStepBuilder, NStepTransition
from ape_x_dqn_tpu.replay.frame_ring import FrameSegmentBuilder
from ape_x_dqn_tpu.runtime.actor import (
    ContinuousPolicyHooks, DiscretePolicyHooks, actor_epsilon,
    feed_sequence, resolve_pending, sequence_ship_after,
    ship_flat_outbox, ship_sequence_outbox)


class _EnvCore:
    """Per-env actor state: eps slot, n-step window, pending
    initial-priority list, optional frame-segment builder."""

    __slots__ = ("eps", "nstep", "pending", "seg")

    def __init__(self, eps: float, nstep: NStepBuilder,
                 seg: FrameSegmentBuilder | None):
        self.eps = eps
        self.nstep = nstep
        self.pending: list[NStepTransition] = []
        self.seg = seg


def _split(out, k: int) -> list:
    """Slice a batched reply pytree into k per-env pytrees."""
    return [jax.tree.map(lambda x, j=j: x[j], out) for j in range(k)]


class VectorActor(DiscretePolicyHooks):
    """Flat-DQN family vector actor. Same constructor/run contract as
    runtime.actor.Actor, except query_fn is the server's `query_batch`
    (inputs carry a leading [K] batch dim). Policy hooks come from the
    shared DiscretePolicyHooks (ContinuousVectorActor swaps in the
    continuous set)."""

    def __init__(self, cfg: RunConfig, actor_index: int,
                 query_fn: Callable[[np.ndarray, int], np.ndarray],
                 transport, seed: int | None = None,
                 episode_callback: Callable[[int, dict], None] | None = None,
                 obs: object | None = None):
        self.cfg = cfg
        self.index = actor_index
        self.query = query_fn
        self.transport = transport
        self.obs = obs if obs is not None else NULL_OBS
        self._hb = f"actor-{actor_index}"
        seed = cfg.seed if seed is None else seed
        self.K = max(cfg.actors.envs_per_actor, 1)
        total_slots = cfg.actors.num_actors * self.K
        envs = []
        self.cores: list[_EnvCore] = []
        frame_ring = (self._ships_frame_segments
                      and getattr(cfg.replay, "storage", "flat")
                      == "frame_ring")
        for j in range(self.K):
            g = actor_index * self.K + j  # global eps-schedule slot
            envs.append(make_env(cfg.env, seed=seed * 10_007 + g,
                                 actor_index=g))
            seg = None
            if frame_ring:
                spec = envs[-1].spec
                assert spec.discrete and len(spec.obs_shape) == 3, \
                    "frame_ring storage needs discrete [H, W, stack] " \
                    "pixel envs"
                seg = FrameSegmentBuilder(
                    cfg.replay.seg_transitions, cfg.learner.n_step,
                    stack=spec.obs_shape[-1])
            self.cores.append(_EnvCore(
                actor_epsilon(g, total_slots, cfg.actors.base_eps,
                              cfg.actors.eps_alpha),
                NStepBuilder(cfg.learner.n_step, cfg.learner.gamma), seg))
        self.venv = SyncVectorEnv(envs)
        self.spec = self.venv.spec
        self.rng = np.random.default_rng(seed * 7919 + actor_index)
        self.episode_callback = episode_callback
        self.frames = 0
        self._frames_unshipped = 0
        self._outbox: list[tuple[NStepTransition, float]] = []

    _ships_frame_segments = True

    # -- priority resolution / shipping (per-env cores, shared outbox) ----

    def _queue(self, core: _EnvCore, t: NStepTransition,
               priority: float) -> None:
        if core.seg is not None:
            core.seg.add(t.action, t.reward, t.discount, t.span, priority)
        else:
            self._outbox.append((t, priority))

    def _resolve_pending(self, core: _EnvCore, out) -> None:
        if not core.pending:
            return
        resolve_pending(core.pending, self._bootstrap_value(out),
                        lambda t, p: self._queue(core, t, p))

    def _ship(self, force: bool = False) -> None:
        if any(c.seg is not None for c in self.cores):
            for core in self.cores:
                segs = (core.seg.flush() if force
                        else core.seg.take_ready())
                for seg in segs:
                    seg["actor"] = self.index
                    seg["frames"] = self._frames_unshipped
                    self._frames_unshipped = 0
                    self.transport.send_experience(seg)
            return
        if not self._outbox:
            return
        if not force and len(self._outbox) < self.cfg.actors.ingest_batch:
            return
        ship_flat_outbox(self._outbox, self._action_array, self.index,
                         self._frames_unshipped, self.transport)
        self._outbox = []
        self._frames_unshipped = 0

    # -- main loop ---------------------------------------------------------

    def run(self, max_frames: int,
            stop_event: threading.Event | None = None) -> int:
        obs = self.venv.reset()  # [K, ...]
        for j, core in enumerate(self.cores):
            if core.seg is not None:
                core.seg.on_reset(obs[j])
        while self.frames < max_frames and not (
                stop_event is not None and stop_event.is_set()):
            self.obs.beat(self._hb)
            with self.obs.span("actor.inference", k=self.K):
                out = self.query(obs, self.K)
            outs = _split(out, self.K)
            actions = []
            for j, core in enumerate(self.cores):
                self._resolve_pending(core, outs[j])
                actions.append(self._select_action(outs[j], core.eps))
            next_obs, rewards, dones, infos = self.venv.step(actions)
            self.frames += self.K
            self._frames_unshipped += self.K
            # per-env n-step append; the autoreset means env j's true
            # post-step observation is terminal_obs when done
            emitted: list[list[NStepTransition]] = []
            trunc_j: list[int] = []
            for j, core in enumerate(self.cores):
                info = infos[j]
                done = bool(dones[j])
                terminal = bool(info.get("terminal", done))
                truncated = done and not terminal
                step_next = info["terminal_obs"] if done else next_obs[j]
                if core.seg is not None:
                    core.seg.on_step(step_next)
                emitted.append(core.nstep.append(
                    obs[j], actions[j], float(rewards[j]), step_next,
                    terminal, truncated,
                    aux=self._taken_value(outs[j], actions[j])))
                if truncated and any(t.discount != 0.0
                                     for t in emitted[-1]):
                    trunc_j.append(j)
            # truncation flushes bootstrap from their terminal obs: one
            # batched query for all truncated envs this step (rare)
            v_term: dict[int, float] = {}
            if trunc_j:
                tb = np.stack([infos[j]["terminal_obs"] for j in trunc_j])
                touts = _split(self.query(tb, len(trunc_j)), len(trunc_j))
                for i, j in enumerate(trunc_j):
                    v_term[j] = self._bootstrap_value(touts[i])
            for j, core in enumerate(self.cores):
                for t in emitted[j]:
                    if t.discount == 0.0:
                        self._queue(core, t, abs(t.reward - float(t.aux)))
                    elif j in v_term:
                        target = t.reward + t.discount * v_term[j]
                        self._queue(core, t, abs(target - float(t.aux)))
                    else:
                        core.pending.append(t)
                if dones[j]:
                    if core.seg is not None:
                        # flushes the open partial segment: segments
                        # never span episodes (autoreset obs seeds next)
                        core.seg.on_reset(next_obs[j])
                    if (self.episode_callback
                            and "episode_return" in infos[j]):
                        self.episode_callback(self.index, infos[j])
            obs = next_obs
            self._ship()
        # shutdown: resolve parked transitions with one final batched
        # forward (their bootstrap obs is each env's current obs)
        if any(core.pending for core in self.cores):
            try:
                outs = _split(self.query(obs, self.K), self.K)
                for j, core in enumerate(self.cores):
                    self._resolve_pending(core, outs[j])
            except Exception:
                for core in self.cores:
                    core.pending.clear()  # server down: drop, don't die
        self._ship(force=True)
        return self.frames


class _RecurrentEnvCore:
    """Per-env sequence actor state: eps slot, sequence builder, the
    state its queries carry (the family's: the LSTM's {c, h}, or the
    decoder's token window), and the one-step-parked record awaiting
    its 1-step TD bootstrap (mirrors runtime.actor.RecurrentActor)."""

    __slots__ = ("eps", "builder", "state", "prev", "_zeros")

    def __init__(self, eps: float, builder, zeros):
        self.eps = eps
        self.builder = builder
        self._zeros = zeros
        self.state: dict = zeros()
        self.prev: dict | None = None

    def zero_state(self) -> None:
        self.state = self._zeros()


class RecurrentVectorActor:
    """Sequence-family vector actor (R2D2, decoder_q): K envs per
    thread, one batched query per vector step that carries each env's
    state beside its observation ({obs, c, h}, {obs, ctx, n} or, for a
    net the server keeps in slots, {obs, slot, fresh}, each with a
    leading [K] axis; runtime/family.py `ACTOR_STATE`), per-env
    SequenceBuilders shipping sequences with what of that state the
    family stores.

    Semantics mirror runtime.actor.RecurrentActor exactly per env
    core — the 1-step pending record, terminal/truncation TD seeds,
    zeroed state on episode end — with the truncation bootstrap
    queries of all truncated envs batched into one extra query per
    vector step (same trick as VectorActor)."""

    def __init__(self, cfg: RunConfig, actor_index: int,
                 query_fn, transport, seed: int | None = None,
                 episode_callback=None, obs: object | None = None):
        from ape_x_dqn_tpu.replay.sequence import SequenceBuilder

        self.cfg = cfg
        self.index = actor_index
        self.query = query_fn
        self.transport = transport
        self.obs = obs if obs is not None else NULL_OBS
        self._hb = f"actor-{actor_index}"
        seed = cfg.seed if seed is None else seed
        self.K = max(cfg.actors.envs_per_actor, 1)
        from ape_x_dqn_tpu.runtime.family import actor_state, episode_state

        self.gamma = cfg.learner.gamma
        self._state_spec = actor_state(cfg)
        total_slots = cfg.actors.num_actors * self.K
        frame_mode = cfg.replay.storage == "frame_ring"
        envs, self.cores = [], []
        for j in range(self.K):
            g = actor_index * self.K + j
            envs.append(make_env(cfg.env, seed=seed * 10_007 + g,
                                 actor_index=g))
            if frame_mode:
                assert len(envs[-1].spec.obs_shape) == 3, \
                    "frame_ring sequence storage needs [H, W, stack] " \
                    "pixel obs"
            self.cores.append(_RecurrentEnvCore(
                actor_epsilon(g, total_slots, cfg.actors.base_eps,
                              cfg.actors.eps_alpha),
                SequenceBuilder(
                    seq_len=cfg.replay.seq_length,
                    overlap=cfg.replay.seq_overlap,
                    priority_eta=cfg.replay.priority_eta,
                    frame_mode=frame_mode,
                    state_keys=self._state_spec.stored),
                # env g's state, and its slot where a server keeps it
                lambda g=g: episode_state(cfg, g)))
        self.venv = SyncVectorEnv(envs)
        self.spec = self.venv.spec
        self.rng = np.random.default_rng(seed * 7919 + actor_index)
        self.episode_callback = episode_callback
        self.frames = 0
        self._frames_unshipped = 0
        self.ship_after = sequence_ship_after(cfg)
        self._outbox: list[dict] = []

    def _feed(self, core: _RecurrentEnvCore, rec: dict, td: float) -> None:
        feed_sequence(self._outbox, core.builder, rec, td)

    def _states(self, cores) -> dict:
        """The cores' carried states, stacked on a leading axis."""
        return {k: np.stack([c.state[k] for c in cores])
                for k in cores[0].state}

    def _resolve_prev(self, core: _RecurrentEnvCore, q_next) -> None:
        """The parked record's 1-step TD bootstrap arrives with the
        next query's Q-values for this env."""
        if core.prev is None:
            return
        td = (core.prev["reward"] + self.gamma * float(np.max(q_next))
              - core.prev["q_sa"])
        self._feed(core, core.prev, td)
        core.prev = None

    def _ship(self, force: bool = False) -> None:
        if not self._outbox:
            return
        if not force and len(self._outbox) < self.ship_after:
            return
        ship_sequence_outbox(self._outbox, self.index,
                             self._frames_unshipped, self.transport)
        self._outbox = []
        self._frames_unshipped = 0

    def run(self, max_frames: int,
            stop_event: threading.Event | None = None) -> int:
        obs = self.venv.reset()
        while self.frames < max_frames and not (
                stop_event is not None and stop_event.is_set()):
            self.obs.beat(self._hb)
            with self.obs.span("actor.inference", k=self.K):
                out = self.query({"obs": obs, **self._states(self.cores)},
                                 self.K)
            q = np.asarray(out["q"])
            after = {k: np.asarray(out[k]) for k in self.cores[0].state}
            actions = []
            for j, core in enumerate(self.cores):
                self._resolve_prev(core, q[j])
                if self.rng.random() < core.eps:
                    actions.append(int(self.rng.integers(
                        self.spec.num_actions)))
                else:
                    actions.append(int(np.argmax(q[j])))
            next_obs, rewards, dones, infos = self.venv.step(actions)
            self.frames += self.K
            self._frames_unshipped += self.K
            # first pass: build records, collect truncation bootstraps
            recs, trunc_j = [], []
            for j, core in enumerate(self.cores):
                info = infos[j]
                done = bool(dones[j])
                terminal = bool(info.get("terminal", done))
                recs.append(dict(
                    obs=obs[j], action=actions[j],
                    reward=float(rewards[j]), terminal=terminal,
                    pre_state=tuple(core.state[k]
                                    for k in self._state_spec.stored),
                    q_sa=float(q[j][actions[j]]), episode_end=done))
                if done and not terminal:
                    trunc_j.append(j)
            # truncation: the sequence ends (state resets) but the
            # bootstrap survives — one batched query on the terminated
            # envs' final observations with their POST-step states
            v_term: dict[int, float] = {}
            if trunc_j:
                tout = self.query({
                    "obs": np.stack([infos[j]["terminal_obs"]
                                     for j in trunc_j]),
                    **{k: v[trunc_j] for k, v in after.items()}},
                    len(trunc_j))
                tq = np.asarray(tout["q"])
                for i, j in enumerate(trunc_j):
                    v_term[j] = float(np.max(tq[i]))
            # second pass: route records, advance/reset the carried state
            for j, core in enumerate(self.cores):
                rec = recs[j]
                if rec["terminal"]:
                    # bootstrap is zero: TD fully determined now
                    self._feed(core, rec, rec["reward"] - rec["q_sa"])
                elif j in v_term:
                    td = (rec["reward"] + self.gamma * v_term[j]
                          - rec["q_sa"])
                    self._feed(core, rec, td)
                else:
                    core.prev = rec
                if dones[j]:
                    core.zero_state()
                    if (self.episode_callback
                            and "episode_return" in infos[j]):
                        self.episode_callback(self.index, infos[j])
                else:
                    core.state = {k: v[j] for k, v in after.items()}
            obs = next_obs
            self._ship()
        # shutdown: resolve parked records with one final batched
        # forward, flush partial sequence tails, ship everything
        if any(core.prev is not None for core in self.cores):
            try:
                out = self.query({"obs": obs, **self._states(self.cores)},
                                 self.K)
                q = np.asarray(out["q"])
                for j, core in enumerate(self.cores):
                    if core.prev is not None:
                        core.prev["episode_end"] = False
                        self._resolve_prev(core, q[j])
            except Exception:  # server down: seed without bootstrap
                for core in self.cores:
                    if core.prev is not None:
                        core.prev["episode_end"] = False
                        self._feed(core, core.prev,
                                   core.prev["reward"]
                                   - core.prev["q_sa"])
                        core.prev = None
        for core in self.cores:
            self._outbox.extend(core.builder.flush())
        self._ship(force=True)
        return self.frames


class ContinuousVectorActor(ContinuousPolicyHooks, VectorActor):
    """Ape-X DPG vector actor: the shared deterministic-policy hooks
    (runtime.actor.ContinuousPolicyHooks) over the vector loop."""

    _ships_frame_segments = False  # DPG obs are low-dimensional

    def __init__(self, cfg: RunConfig, actor_index: int,
                 query_fn, transport, seed: int | None = None,
                 episode_callback=None, obs: object | None = None):
        super().__init__(cfg, actor_index, query_fn, transport, seed=seed,
                         episode_callback=episode_callback, obs=obs)
        self._init_noise(cfg)
