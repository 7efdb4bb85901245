"""Eval worker: greedy-policy evaluation episodes and the HNS suite.

Reference parity (SURVEY.md §2.2 "Eval worker", §5 metrics): a periodic
evaluator running near-greedy (eps = 0.001) episodes whose *unclipped*
returns feed the Atari-57 median human-normalized score — the north-star
metric (BASELINE.json `metric`). Evaluation shares the batched TPU
inference server with the actors (one more client on the same jit), so no
separate device or params copy is needed.

Eval episodes differ from training episodes in the standard ways: no
episodic-life pseudo-terminals, no reward clipping, near-greedy policy.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Iterable

import numpy as np

from ape_x_dqn_tpu.configs import RunConfig
from ape_x_dqn_tpu.envs import make_env
from ape_x_dqn_tpu.utils.metrics import ATARI_HUMAN_RANDOM, median_hns


class EvalWorker:
    """Runs greedy eval episodes against a Q-value query function."""

    def __init__(self, cfg: RunConfig, query_fn: Callable,
                 game: str | None = None, seed: int | None = None,
                 policy_factory: Callable[[], Callable] | None = None):
        """query_fn(obs) -> q-values [A] (e.g. inference server .query).

        policy_factory, when given, builds a fresh per-episode policy
        (obs -> q-values for discrete envs, obs -> action for continuous)
        — recurrent policies carry (c, h) across the episode's queries,
        continuous ones route through the deterministic DPG actor.
        """
        self.cfg = cfg
        env_cfg = cfg.env
        if game is not None:
            if env_cfg.id == "atari57":
                # a per-game eval env for a multi-game net must keep
                # the shared 18-action legal set the net was sized for
                env_cfg = dataclasses.replace(env_cfg,
                                              full_action_set=True)
            env_cfg = dataclasses.replace(env_cfg, id=game)
        if env_cfg.kind in ("atari", "synthetic_atari"):
            env_cfg = dataclasses.replace(env_cfg, episodic_life=False,
                                          clip_rewards=False)
        seed = (cfg.seed + 977_231) if seed is None else seed
        self.env = make_env(env_cfg, seed=seed)
        self.query = query_fn
        self.policy_factory = policy_factory
        self.eps = cfg.eval_eps
        self.rng = np.random.default_rng(seed)
        # eval_max_frames is specified in RAW env frames (the Atari
        # protocol's 108k = 30 min @ 60Hz) but the episode loop counts
        # AGENT steps; a skipped env consumes frame_skip raw frames per
        # step. Counting steps against the raw budget made the cap 4x
        # looser than documented — on slow-link hosts that blew the
        # whole final-eval deadline on one episode (round-5 suite run:
        # a trained game recorded eval=null and was discarded).
        self._frames_per_step = (
            env_cfg.frame_skip
            if env_cfg.kind in ("atari", "synthetic_atari") else 1)

    def run_episode(self, max_frames: int = 108_000,
                    stop_event=None,
                    deadline: float | None = None) -> float | None:
        """One episode; returns the unclipped episode return, or None if
        stop_event fired / the wall-clock deadline passed mid-episode
        (the partial return is meaningless)."""
        policy = (self.policy_factory() if self.policy_factory is not None
                  else self.query)
        discrete = self.env.spec.discrete
        obs = self.env.reset()
        ep_return = 0.0
        for _ in range(max(max_frames // self._frames_per_step, 1)):
            if stop_event is not None and stop_event.is_set():
                return None
            if deadline is not None and time.monotonic() > deadline:
                return None
            if not discrete:
                action = np.asarray(policy(obs))  # deterministic mu(s)
            else:
                # always query (recurrent policies must advance their
                # state every step), then eps-explore on top
                q = policy(obs)
                if self.rng.random() < self.eps:
                    action = int(
                        self.rng.integers(self.env.spec.num_actions))
                else:
                    action = int(np.argmax(q))
            obs, reward, done, info = self.env.step(action)
            ep_return += info.get("raw_reward", reward)
            if done:
                # prefer the env's own unclipped accounting when present
                return float(info.get("episode_return", ep_return))
        return ep_return

    def run(self, episodes: int, max_frames: int = 108_000,
            stop_event=None, deadline_s: float | None = None) -> dict | None:
        """Aggregate stats over episodes; None if cancelled before any
        episode completed. deadline_s bounds the whole evaluation's
        wall-clock (needed at shutdown, where an unbounded greedy policy
        could otherwise block the driver for minutes)."""
        deadline = (time.monotonic() + deadline_s
                    if deadline_s is not None else None)
        returns = []
        for _ in range(episodes):
            r = self.run_episode(max_frames, stop_event=stop_event,
                                 deadline=deadline)
            if r is None:
                break
            returns.append(r)
        if not returns:
            return None
        return {
            "episodes": len(returns),
            "mean_return": float(np.mean(returns)),
            "median_return": float(np.median(returns)),
            "min_return": float(np.min(returns)),
            "max_return": float(np.max(returns)),
        }


def run_eval_measured(worker: "EvalWorker", episodes: int, server,
                      stop_event=None,
                      deadline_s: float | None = None,
                      max_frames: int = 108_000
                      ) -> tuple[dict | None, int]:
    """Run worker.run while polling the shared inference server's
    queue depth at ~20Hz; returns (result, max depth seen DURING the
    eval). The during-eval max is the back-pressure the eval induces
    on concurrent actors — a post-eval snapshot mostly reads 0 because
    actors drain the queue the moment the eval stops querying
    (round-3 advisor finding on server_queue_depth)."""
    import threading

    depth = {"max": int(server.queue_depth)}
    done = threading.Event()

    def poll():
        while not done.wait(0.05):
            depth["max"] = max(depth["max"], int(server.queue_depth))

    t = threading.Thread(target=poll, name="eval-depth-poll", daemon=True)
    t.start()
    try:
        res = worker.run(episodes, max_frames=max_frames,
                         stop_event=stop_event, deadline_s=deadline_s)
    finally:
        done.set()
        t.join(timeout=1.0)
    return res, depth["max"]


ATARI57_GAMES: tuple[str, ...] = tuple(sorted(ATARI_HUMAN_RANDOM))


def eval_game_rotation(cfg: RunConfig) -> tuple[bool, tuple[str, ...]]:
    """Whether a run's periodic eval should rotate through the suite,
    and the game list. Multi-game runs (env id='atari57') must rotate:
    a fixed eval worker would silently measure only the alphabetically-
    first game every time. ONE predicate for both drivers — the
    rotation rule diverging between them is exactly the bug it fixes."""
    rotate = (cfg.env.id == "atari57"
              and cfg.env.kind in ("atari", "synthetic_atari"))
    return rotate, ATARI57_GAMES


class RollingSuiteScore:
    """Rolling per-game score table for the multi-game eval rotation.

    The rotation evaluates ONE game per eval event, so a full suite
    view previously needed an offline `--eval-only` pass over all 57
    (round-3 verdict weak #7). This keeps the latest unclipped return
    per game and exposes a rolling backend-marked median-HNS over the
    games seen so far — the same honesty split as evaluate_suite: the
    unqualified key never appears for synthetic backends, and the
    rolling key is additionally marked `rolling_` because it medians
    only the games evaluated so far this run."""

    def __init__(self, cfg: RunConfig):
        from ape_x_dqn_tpu.envs.atari import atari_backend

        self._backend = atari_backend(cfg.env.kind)
        self._scores: dict[str, float] = {}

    def update(self, game: str, mean_return: float) -> dict:
        """Record a game's latest eval; returns metric fields to log."""
        self._scores[game] = float(mean_return)
        known = {g: s for g, s in self._scores.items()
                 if g in ATARI_HUMAN_RANDOM}
        key = ("rolling_median_hns" if self._backend == "ale"
               else "rolling_median_hns_synthetic")
        out = {"eval_games_seen": len(self._scores)}
        if known:
            out[key] = median_hns(known)
        return out

    @property
    def scores(self) -> dict[str, float]:
        return dict(self._scores)


def final_eval_game(cfg: RunConfig) -> str | None:
    """The game for a driver's guaranteed end-of-run fallback eval.
    Multi-game (rotating) configs must not fall back to an unmarked
    default worker — that silently measures the alphabetically-first
    game (round-3 advisor finding). ONE helper for both drivers, for
    the same reason eval_game_rotation is shared."""
    rotate, games = eval_game_rotation(cfg)
    return games[0] if rotate else None


def make_eval_policy_factory(family: str, cfg: RunConfig,
                             query_fn: Callable) -> Callable | None:
    """Per-episode eval policy builder per model family (shared by
    ApexDriver's eval loop and the standalone suite runner).

    Sequence families carry the state their queries carry (the LSTM's
    fresh (c, h), the decoder's token window, or the name of the slot
    that holds the decoder's state in the server: family.ACTOR_STATE)
    across one episode's queries; continuous policies return the
    deterministic action mu(s); plain Q-nets need no factory
    (EvalWorker queries directly).
    """
    from ape_x_dqn_tpu.runtime.family import (
        ACTOR_STATE, episode_state, slot_geometry)

    if family == "dpg":
        return lambda: lambda obs: query_fn(obs)["a"]
    if family not in ACTOR_STATE:
        return None

    def factory():
        # the eval worker's slot, where the state lives in the server,
        # is the one behind the fleet's
        state = episode_state(cfg, slot_geometry(cfg)[0] - 1)

        def policy(obs):
            out = query_fn({"obs": obs, **state})
            state.update({k: out[k] for k in state})
            return out["q"]

        return policy

    return factory


def evaluate_suite(cfg: RunConfig, query_fn: Callable,
                   games: Iterable[str] | None = None,
                   episodes_per_game: int | None = None,
                   max_frames: int = 108_000,
                   policy_factory: Callable | None = None) -> dict:
    """Per-game greedy scores -> median human-normalized score.

    The Atari-57 harness (SURVEY.md §2.1 config 3): loops the suite,
    evaluates each game with the shared query_fn, and aggregates the
    north-star metric. Returns {"scores": {game: mean}, "hns":
    {game: hns}, "backends": {game: "ale"|"synthetic"}, and EITHER
    "median_hns" (every game ran on the real ALE) OR
    "median_hns_synthetic" (any game ran the in-image catch stand-in).

    The split key is deliberate: in an image without `ale_py`, make_env
    silently substitutes SyntheticAtari for every game, and an unmarked
    "median_hns" from that path would look exactly like the north-star
    number while measuring a catch game. The real key only ever appears
    when the real backend produced it.
    """
    from ape_x_dqn_tpu.envs.atari import atari_backend

    games = tuple(games) if games is not None else ATARI57_GAMES
    # at least one episode: worker.run(0) returns None, and a suite
    # score of None is useless (configs legitimately carry
    # eval_episodes=0 to disable the TRAINING-time eval loop)
    episodes = max(episodes_per_game or cfg.eval_episodes, 1)
    backend = atari_backend(cfg.env.kind)
    scores: dict[str, float] = {}
    for game in games:
        worker = EvalWorker(cfg, query_fn, game=game,
                            policy_factory=policy_factory)
        scores[game] = worker.run(episodes, max_frames)["mean_return"]
    known = {g: s for g, s in scores.items() if g in ATARI_HUMAN_RANDOM}
    from ape_x_dqn_tpu.utils.metrics import human_normalized_score
    out = {
        "scores": scores,
        "hns": {g: human_normalized_score(g, s) for g, s in known.items()},
        "backends": {g: backend for g in scores},
    }
    key = "median_hns" if backend == "ale" else "median_hns_synthetic"
    out[key] = median_hns(known)
    return out


def run_suite_eval(cfg: RunConfig, games: Iterable[str] | None = None,
                   episodes_per_game: int | None = None,
                   checkpoint_dir: str | None = None,
                   max_frames: int = 108_000) -> dict:
    """Standalone evaluation entry (CLI --eval-only): build the net,
    restore the latest checkpoint's params, and run greedy episodes —
    the full HNS suite for Atari configs, the config's own env
    otherwise. No learner, no actors, no training state.
    """
    import jax

    from ape_x_dqn_tpu.envs import make_env
    from ape_x_dqn_tpu.models import build_network
    from ape_x_dqn_tpu.runtime.family import (
        family_of, family_setup, keeps_slots, server_apply_fn,
        server_slots)

    if games is not None and cfg.env.kind not in ("atari",
                                                  "synthetic_atari"):
        # an explicit --games list builds per-game Atari envs, whose
        # 84x84x4 observations cannot feed a network sized for this
        # config's own env — fail with a clear message instead of an
        # opaque downstream shape mismatch
        raise ValueError(
            f"--games is only valid for Atari configs (env.kind 'atari' "
            f"or 'synthetic_atari'), got kind={cfg.env.kind!r}")
    family = family_of(cfg)
    probe = make_env(cfg.env, seed=cfg.seed)
    spec = probe.spec
    net = build_network(cfg.network, spec)
    params = family_setup(cfg, spec, net, probe.reset()).params
    if family == "dpg":
        params = {"actor": params[0], "critic": params[1]}
    restored_step = None
    if checkpoint_dir:
        from ape_x_dqn_tpu.utils.checkpoint import CheckpointManager
        mngr = CheckpointManager(checkpoint_dir)
        restored_step = mngr.latest_step()
        if restored_step is not None:
            # raw restore (no template): we only need the param leaves,
            # and the saved tree holds the full TrainState minus replay
            raw = mngr.restore(restored_step)
            if family == "dpg":
                params = {"actor": raw["actor_params"],
                          "critic": raw["critic_params"]}
            else:
                params = raw["params"]
        mngr.close()

    server = None
    if keeps_slots(net):
        # the state lives in the server between queries: the episode is
        # served as the fleet's are
        from ape_x_dqn_tpu.parallel.inference_server import (
            BatchedInferenceServer)

        server = BatchedInferenceServer(
            server_apply_fn(family, net, cfg), params, max_batch=1,
            **server_slots(cfg, net))
        query = server.query
    else:
        fn = jax.jit(server_apply_fn(family, net))

        def query(inp):
            batched = jax.tree.map(lambda x: np.asarray(x)[None], inp)
            return jax.tree.map(lambda x: np.asarray(x)[0],
                                fn(params, batched))

    factory = make_eval_policy_factory(family, cfg, query)
    if games is None and cfg.env.kind not in ("atari", "synthetic_atari"):
        worker = EvalWorker(cfg, query, policy_factory=factory)
        out = worker.run(max(episodes_per_game or cfg.eval_episodes, 1),
                         max_frames)
    else:
        out = evaluate_suite(cfg, query, games=games,
                             episodes_per_game=episodes_per_game,
                             max_frames=max_frames,
                             policy_factory=factory)
    if server is not None:
        server.stop()
    out["restored_step"] = restored_step
    return out
