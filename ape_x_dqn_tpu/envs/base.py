"""Environment interface.

Host-side (numpy) environment API used by actors and the eval worker.
Reference parity: the reference's env layer wraps ALE / CartPole / DM
Control (SURVEY.md §1 layer 1). This image has none of those packages, so
the framework ships native implementations (CartPole physics, a synthetic
ALE-compatible game, pendulum swing-up) and gates the real backends behind
imports — a user with `ale_py` / `dm_control` installed gets the real
games through the same wrapper stack.
"""

from __future__ import annotations

import abc
from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class EnvSpec:
    """Static description of an environment's interfaces."""

    obs_shape: tuple[int, ...]
    obs_dtype: np.dtype
    discrete: bool
    num_actions: int = 0  # discrete only
    action_dim: int = 0  # continuous only
    action_low: float = -1.0
    action_high: float = 1.0


class Env(abc.ABC):
    """Minimal synchronous env: reset() -> obs, step(a) -> (obs, r, done, info).

    `done` is episode termination (true terminal OR time limit); `info` may
    carry `terminal` (bootstrapping-relevant termination, i.e. excluding
    time limits), `lives`, and `episode_return` on episode end.
    """

    spec: EnvSpec

    @abc.abstractmethod
    def reset(self) -> np.ndarray:
        ...

    @abc.abstractmethod
    def step(self, action) -> tuple[np.ndarray, float, bool, dict]:
        ...

    def seed(self, seed: int) -> None:  # pragma: no cover - default noop
        pass


def make_env(cfg, seed: int = 0, actor_index: int = 0) -> Env:
    """Factory from an EnvConfig (ape_x_dqn_tpu.configs.EnvConfig)."""
    from ape_x_dqn_tpu.envs import atari, cartpole, control

    kind = cfg.kind
    if kind == "cartpole":
        return cartpole.CartPole(seed=seed)
    if kind == "cartpole_po":
        return cartpole.MaskedCartPole(seed=seed)
    if kind in ("atari", "synthetic_atari"):
        return atari.make_atari(cfg, seed=seed, actor_index=actor_index)
    if kind == "control":
        return control.make_control(cfg, seed=seed)
    if kind == "synthetic_tokens":
        from ape_x_dqn_tpu.envs.tokens import SyntheticTokens

        return SyntheticTokens(cfg.num_tokens, seed=seed,
                               max_episode_frames=cfg.max_episode_frames)
    raise ValueError(f"unknown env kind {kind!r}")
