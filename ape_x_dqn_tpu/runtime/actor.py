"""Actor runtime (SURVEY.md §2.2 "Actor runtime", §3.1 Actor_i loop).

One actor thread steps a SyncVectorEnv of K = `actors.envs_per_actor`
CPU envs (K >= 1) and sends ONE K-item query per vector step to the
batched TPU inference server (`BatchedInferenceServer.query_batch`), so
the server sees batch-K work from a single thread and the per-step RPC
cost amortizes K ways (SURVEY.md §2.3 item 4, §2.4 "inference batching
parallelism", §7 hard part 3). There is one class a family (`Actor`,
`ContinuousActor`, `RecurrentActor`; runtime/family.py `actor_class`):
K is a size, not a choice of code path.

Each env core owns a distinct slot of the global Horgan et al. 2018
eps schedule, eps_g = base ** (1 + alpha * g / (N-1)): actor i's env j
is global slot g = i*K+j of N = num_actors*K.

Per-env bookkeeping (n-step building, initial-priority resolution,
frame-segment assembly) is host-side numpy per env core. Priorities are
computed actor-side (so fresh experience enters the sum-tree with real
TD magnitudes, not a max-priority hack): a transition emitted at step t
needs max_a Q(s_{t+n}); the actor has Q(s_t..) from action selection,
and Q(s_{t+n}) is env j's slice of the NEXT vector query — so
non-terminal transitions park in a one-step pending list per env.
Terminal transitions (discount 0) resolve immediately, truncation
flushes through one extra query per vector step that batches the
truncated envs' terminal observations.
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
from ape_x_dqn_tpu.replay.sequence import (
    SequenceBuilder, split_priorities, stack_items)


def actor_epsilon(i: int, n: int, base: float = 0.4,
                  alpha: float = 7.0) -> float:
    if n <= 1:
        return base
    return base ** (1.0 + alpha * i / (n - 1))


def _split(out, k: int) -> list:
    """Slice a batched reply pytree into k per-env pytrees."""
    return [jax.tree.map(lambda x, j=j: x[j], out) for j in range(k)]


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


class Actor:
    """Flat-DQN family: discrete eps-greedy over Q-values [K, A]. Also
    the base of ContinuousActor, which overrides the policy hooks, and
    of RecurrentActor, which keeps the construction scaffolding (envs on
    the global eps / seed schedule, frame accounting) and replaces the
    flat n-step pipeline.

    Hooks: `_select_action` (policy out + eps -> action),
    `_bootstrap_value` (policy out -> V(s) estimate for n-step
    targets), `_taken_value` (policy out + action -> the value whose TD
    error seeds the initial priority), `_action_array` (stacking dtype
    for shipment)."""

    # frame-ring shipping (replay/frame_ring.py): transitions leave as
    # fixed segments of single frames instead of stacked obs pairs.
    # Only the flat discrete family ships segments — RecurrentActor makes
    # its own cores and handles frame-mode inside its SequenceBuilder.
    _ships_frame_segments = True

    def __init__(self, cfg: RunConfig, actor_index: int,
                 query_fn: Callable, transport, seed: int | None = None,
                 episode_callback: Callable[[int, dict], None] | None = None,
                 obs: object | None = None):
        """query_fn(inputs, k) -> outputs, both with a leading [k] axis
        (the inference server's .query_batch). obs: optional
        obs.core.Obs facade — inference/env-step spans + the actor-{i}
        heartbeat (NULL_OBS when omitted)."""
        self.cfg = cfg
        self.index = actor_index
        self.query = query_fn
        self.transport = transport
        self.obs = obs if obs is not None else NULL_OBS
        self._hb = f"actor-{actor_index}"
        seed = cfg.seed if seed is None else seed
        self.K = max(cfg.actors.envs_per_actor, 1)
        total_slots = cfg.actors.num_actors * self.K
        envs, self.cores = [], []
        for j in range(self.K):
            g = actor_index * self.K + j  # global eps-schedule slot
            envs.append(make_env(cfg.env, seed=seed * 10_007 + g,
                                 actor_index=g))
            self.cores.append(self._core(g, envs[-1], actor_epsilon(
                g, total_slots, cfg.actors.base_eps, cfg.actors.eps_alpha)))
        self.venv = SyncVectorEnv(envs)
        self.spec = self.venv.spec
        self.rng = np.random.default_rng(seed * 7919 + actor_index)
        self.episode_callback = episode_callback
        self.frames = 0
        self._frames_unshipped = 0
        self._outbox: list = []

    def _core(self, g: int, env, eps: float) -> _EnvCore:
        cfg, seg = self.cfg, None
        if (self._ships_frame_segments
                and getattr(cfg.replay, "storage", "flat") == "frame_ring"):
            spec = env.spec
            assert spec.discrete and len(spec.obs_shape) == 3, \
                "frame_ring storage needs discrete [H, W, stack] pixel envs"
            seg = FrameSegmentBuilder(
                cfg.replay.seg_transitions, cfg.learner.n_step,
                stack=spec.obs_shape[-1])
        return _EnvCore(
            eps, NStepBuilder(cfg.learner.n_step, cfg.learner.gamma), seg)

    # -- policy hooks ------------------------------------------------------

    def _select_action(self, out, eps: float):
        if self.rng.random() < eps:
            return int(self.rng.integers(self.spec.num_actions))
        return int(np.argmax(out))

    def _bootstrap_value(self, out) -> float:
        return float(np.max(out))

    def _taken_value(self, out, action) -> float:
        return float(out[action])

    def _action_array(self, ts: list[NStepTransition]) -> np.ndarray:
        return np.asarray([t.action for t in ts], np.int32)

    # -- priority resolution / shipping (per-env cores, shared outbox) ----

    def _queue(self, core: _EnvCore, t: NStepTransition,
               priority: float) -> None:
        """A transition's initial priority is resolved: hand it to the
        shipping pipeline. Callers always queue in start-step order (the
        pending list drains before any newer transition routes), which
        the frame-segment builder relies on."""
        if core.seg is not None:
            core.seg.add(t.action, t.reward, t.discount, t.span, priority)
        else:
            self._outbox.append((t, priority))

    def _resolve_pending(self, core: _EnvCore, out) -> None:
        """Resolve parked transitions with the just-arrived bootstrap
        value (max_a Q of each transition's next_obs): their initial
        priority is the |TD| against the value the actor stashed at
        selection time."""
        if not core.pending:
            return
        v_next = self._bootstrap_value(out)
        for t in core.pending:
            target = t.reward + t.discount * v_next
            self._queue(core, t, abs(target - float(t.aux)))
        core.pending.clear()

    def _ship_segments(self, force: bool) -> None:
        shipped = 0
        for core in self.cores:
            for seg in (core.seg.flush() if force
                        else core.seg.take_ready()):
                seg["actor"] = self.index
                # env-frame accounting rides the first segment
                seg["frames"] = self._frames_unshipped
                self._frames_unshipped = 0
                self.transport.send_experience(seg)
                shipped += 1
        if shipped:
            self.obs.mark("actor.ship", segments=shipped)

    def _ship(self, force: bool = False) -> None:
        if self.cores[0].seg is not None:
            self._ship_segments(force)
            return
        if not self._outbox:
            return
        if not force and len(self._outbox) < self.cfg.actors.ingest_batch:
            return
        # the wire format of a batch of flat n-step transitions (the
        # ingest staging and transition_item_spec depend on these keys)
        ts = [t for t, _ in self._outbox]
        self.transport.send_experience({
            "obs": np.stack([t.obs for t in ts]),
            "action": self._action_array(ts),
            "reward": np.asarray([t.reward for t in ts], np.float32),
            "next_obs": np.stack([t.next_obs for t in ts]),
            "discount": np.asarray([t.discount for t in ts], np.float32),
            "priorities": np.asarray([p for _, p in self._outbox],
                                     np.float32),
            "actor": self.index,
            "frames": self._frames_unshipped,
        })
        self._outbox = []
        self._frames_unshipped = 0
        self.obs.mark("actor.ship", rows=len(ts))

    # -- main loop ---------------------------------------------------------

    def _running(self, max_frames: int,
                 stop_event: threading.Event | None) -> bool:
        return self.frames < max_frames and not (
            stop_event is not None and stop_event.is_set())

    def _step_envs(self, actions: list):
        with self.obs.span("actor.env_step", k=self.K):
            stepped = self.venv.step(actions)
        self.frames += self.K
        self._frames_unshipped += self.K
        return stepped

    def _episode_over(self, info: dict) -> None:
        if self.episode_callback and "episode_return" in info:
            self.episode_callback(self.index, info)

    def run(self, max_frames: int,
            stop_event: threading.Event | None = None) -> int:
        obs = self.venv.reset()  # [K, ...]
        for j, core in enumerate(self.cores):
            if core.seg is not None:
                core.seg.on_reset(obs[j])
        while self._running(max_frames, stop_event):
            self.obs.beat(self._hb)
            with self.obs.span("actor.inference", k=self.K):
                out = self.query(obs, self.K)
            outs = _split(out, self.K)
            actions = []
            for j, core in enumerate(self.cores):
                self._resolve_pending(core, outs[j])
                actions.append(self._select_action(outs[j], core.eps))
            next_obs, rewards, dones, infos = self._step_envs(actions)
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
            # truncation flushes bootstrap from their terminal obs, which
            # won't be queried again: one batched query for all truncated
            # envs this step (rare)
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
                    self._episode_over(infos[j])
            obs = next_obs
            self._ship()
        # shutdown: resolve parked transitions (waiting on Q(s_{t+n}),
        # each env's current obs) with one final batched forward so they
        # aren't dropped
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


class ContinuousActor(Actor):
    """Ape-X DPG actor: deterministic policy + Gaussian exploration noise.

    Horgan et al. 2018 "Ape-X DPG" (SURVEY.md §2.1 config 5): actions are
    mu(s) + N(0, sigma^2) clipped to the action box, with sigma from
    ActorConfig.noise_sigma (scaled by the box half-range). The inference
    server evaluates both the policy and the critic in one batched
    forward — {"a": mu(s), "q": Q(s, mu(s))} — so actors compute initial
    priorities from the critic's value estimates exactly like discrete
    actors do from max-Q (same one-step pending mechanism).
    """

    _ships_frame_segments = False  # DPG obs are low-dimensional

    def __init__(self, cfg: RunConfig, *args, **kwargs):
        super().__init__(cfg, *args, **kwargs)
        self._noise_scale = (cfg.actors.noise_sigma
                             * (self.spec.action_high
                                - self.spec.action_low) / 2.0)

    def _select_action(self, out, eps: float):
        # eps is unused: continuous exploration is additive noise
        noise = self.rng.normal(0.0, self._noise_scale,
                                size=self.spec.action_dim)
        return np.clip(np.asarray(out["a"], np.float32) + noise,
                       self.spec.action_low,
                       self.spec.action_high).astype(np.float32)

    def _bootstrap_value(self, out) -> float:
        return float(out["q"])

    def _taken_value(self, out, action) -> float:
        # Q(s, mu(s)) stands in for Q(s, a_taken): the noise
        # perturbation is small, and this only seeds initial priority
        return float(out["q"])

    def _action_array(self, ts: list[NStepTransition]) -> np.ndarray:
        return np.stack([np.asarray(t.action, np.float32) for t in ts])


class _RecurrentEnvCore:
    """Per-env sequence actor state: eps slot, sequence builder, the
    state its queries carry (the family's: the LSTM's {c, h}, the
    decoder's token window, or its slot), and the one-step-parked
    record awaiting its 1-step TD bootstrap."""

    __slots__ = ("eps", "builder", "state", "prev", "_zeros")

    def __init__(self, eps: float, builder, zeros):
        self.eps = eps
        self.builder = builder
        self._zeros = zeros
        self.state: dict = zeros()
        self.prev: dict | None = None

    def zero_state(self) -> None:
        self.state = self._zeros()


class RecurrentActor(Actor):
    """Sequence-family actor (R2D2, decoder_q): one batched query per
    vector step that carries each env's state beside its observation
    ({obs, c, h}, {obs, ctx, n} or, for a net the server keeps in
    slots, {obs, slot, fresh}, each with a leading [K] axis;
    runtime/family.py `ACTOR_STATE`) and gets it back beside `q`, so
    the batched TPU forward serves many envs' recurrent steps at once
    (SURVEY.md §3.2); per-env SequenceBuilders ship sequences with what
    of that state the family stores.

    Initial sequence priorities are computed actor-side from 1-step TD
    estimates (the n-step-in-sequence TD is the learner's job; the 1-step
    |TD| eta-mix is the same fresh-experience signal at a fraction of the
    bookkeeping). A step's TD needs max_a Q(s_{t+1}), which arrives at
    the *next* query — so each step parks for one iteration before
    entering the SequenceBuilder (mirroring Actor's pending list). A
    terminal's TD needs no bootstrap; the truncated envs' bootstraps
    come from one extra batched query per vector step; an episode's end
    zeroes the env's state.

    Frame-mode shipping (replay storage "frame_ring") happens inside the
    SequenceBuilder (single frames per sequence), not via Actor's
    flat-transition segment path.
    """

    def __init__(self, cfg: RunConfig, *args, **kwargs):
        from ape_x_dqn_tpu.runtime.family import actor_state

        self.gamma = cfg.learner.gamma
        # what a query carries beside the observation, and which of it
        # a sequence stores: the family's row (runtime/family.py)
        self._state_spec = actor_state(cfg)
        super().__init__(cfg, *args, **kwargs)
        # ingest_batch counts TRANSITIONS, so sequences ship in
        # proportionally smaller groups to keep ingest latency comparable
        self.ship_after = max(
            1, cfg.actors.ingest_batch // cfg.replay.seq_length)

    def _core(self, g: int, env, eps: float) -> _RecurrentEnvCore:
        from ape_x_dqn_tpu.runtime.family import episode_state

        cfg = self.cfg
        frame_mode = cfg.replay.storage == "frame_ring"
        if frame_mode:
            assert len(env.spec.obs_shape) == 3, \
                "frame_ring sequence storage needs [H, W, stack] pixel obs"
        return _RecurrentEnvCore(
            eps,
            SequenceBuilder(
                seq_len=cfg.replay.seq_length, overlap=cfg.replay.seq_overlap,
                priority_eta=cfg.replay.priority_eta, frame_mode=frame_mode,
                state_keys=self._state_spec.stored),
            # env g's state, and its slot where a server keeps it
            lambda: episode_state(cfg, g))

    def _feed(self, core: _RecurrentEnvCore, rec: dict, td: float) -> None:
        """Append one step record to the env's SequenceBuilder, routing
        any completed sequence items into the outbox."""
        self._outbox.extend(core.builder.append(
            rec["obs"], rec["action"], rec["reward"], rec["terminal"],
            rec["pre_state"], td=td, episode_end=rec["episode_end"]))

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
        # the wire format of a batch of sequences (sequence_item_spec
        # depends on it)
        items, pris = split_priorities(self._outbox)
        batch = stack_items(items)
        batch["priorities"] = pris
        batch["actor"] = self.index
        batch["frames"] = self._frames_unshipped
        self.transport.send_experience(batch)
        self._outbox = []
        self._frames_unshipped = 0
        self.obs.mark("actor.ship", sequences=len(items))

    def run(self, max_frames: int,
            stop_event: threading.Event | None = None) -> int:
        obs = self.venv.reset()
        while self._running(max_frames, stop_event):
            self.obs.beat(self._hb)
            with self.obs.span("actor.inference", k=self.K):
                out = self.query({"obs": obs, **self._states(self.cores)},
                                 self.K)
            q = np.asarray(out["q"])
            after = {k: np.asarray(out[k]) for k in self.cores[0].state}
            actions = []
            for j, core in enumerate(self.cores):
                self._resolve_prev(core, q[j])
                actions.append(self._select_action(q[j], core.eps))
            next_obs, rewards, dones, infos = self._step_envs(actions)
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
                    self._episode_over(infos[j])
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
