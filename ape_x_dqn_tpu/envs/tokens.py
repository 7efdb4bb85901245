"""Synthetic token environment: the stand-in for a language agent's
episode, as envs/atari.py's synthetic game stands in for the ALE.

One observation is ONE token id (int32 scalar) from the `num_tokens`
ids the Q-network holds; an action is the next token, an id of the same
range. The stream has a learnable rule with sparse feedback: the id
that follows `o` is `(a + o * 31 + 7) mod V` for the chosen action `a`,
every `REWARD_EVERY`-th step pays +1 if the action was the "right" one
for the observation (`(o * 17 + 3) mod V`) and -1 if not, 0 in between,
and an episode ends (a true terminal) at a seeded length between
`MIN_LEN` and `max_episode_frames`. No tokenizer, no text: what the
system needs from an environment is the spec and a stream whose shapes
are those of real traffic (runtime/family.py builds replay, learner and
server from `spec`).
"""

from __future__ import annotations

import numpy as np

from ape_x_dqn_tpu.envs.base import Env, EnvSpec

REWARD_EVERY = 16
MIN_LEN = 24
MAX_LEN = 4096


class SyntheticTokens(Env):
    def __init__(self, num_tokens: int, seed: int = 0,
                 max_episode_frames: int = MAX_LEN):
        if num_tokens < 2:
            raise ValueError("synthetic_tokens needs env.num_tokens >= 2")
        self.spec = EnvSpec(obs_shape=(), obs_dtype=np.dtype(np.int32),
                            discrete=True, num_actions=int(num_tokens))
        self._v = int(num_tokens)
        self._max_len = max(min(int(max_episode_frames), MAX_LEN),
                            MIN_LEN + 1)
        self._rng = np.random.default_rng(seed)
        self._obs = 0
        self._t = 0
        self._len = self._max_len
        self._ret = 0.0

    def seed(self, seed: int) -> None:
        self._rng = np.random.default_rng(seed)

    def reset(self) -> np.ndarray:
        self._obs = int(self._rng.integers(self._v))
        self._t = 0
        self._len = int(self._rng.integers(MIN_LEN, self._max_len + 1))
        self._ret = 0.0
        return np.int32(self._obs)

    def step(self, action) -> tuple[np.ndarray, float, bool, dict]:
        a, o = int(action), self._obs
        self._t += 1
        reward = 0.0
        if self._t % REWARD_EVERY == 0:
            reward = 1.0 if a == (o * 17 + 3) % self._v else -1.0
        self._ret += reward
        self._obs = (a + o * 31 + 7) % self._v
        done = self._t >= self._len
        info: dict = {"terminal": done}
        nxt = np.int32(self._obs)
        if done:
            info["episode_return"] = self._ret
        return nxt, reward, done, info
