"""Recurrent LSTM Q-network for the R2D2 config.

CNN torso -> LSTM -> dueling heads with replay-stored recurrent state
(SURVEY.md §2.2 "LSTM Q-net", §3.4). The time unroll is `nn.scan` over an
`OptimizedLSTMCell`, i.e. a `lax.scan` inside the learner jit — static
sequence length, no Python-level recurrence (XLA-friendly control flow).

Two entry points sharing parameters (same submodule names):
- `__call__(obs[B,T,...], state)` — full-sequence unroll for the learner
  (burn-in + train segments are sliced by the loss, not the net).
- `step(obs[B,...], state)` — single step for actors / inference server.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from ape_x_dqn_tpu.models.base import dtype_of, preprocess_obs
from ape_x_dqn_tpu.models.qnets import DuelingHead, NatureCNNTorso

LSTMState = tuple[jax.Array, jax.Array]  # (c, h), float32 in replay


class ApeXLSTMQNet(nn.Module):
    num_actions: int
    lstm_size: int = 512
    dense: int = 512
    dueling: bool = True
    compute_dtype: str = "bfloat16"
    mlp_torso: bool = False  # dense torso for vector-obs tests/smoke
    mlp_hidden: int = 128

    def _torso(self, obs: jax.Array, dt) -> jax.Array:
        x = preprocess_obs(obs, dt)
        if self.mlp_torso:
            return nn.relu(nn.Dense(self.mlp_hidden, dtype=dt,
                                    name="torso")(x))
        return NatureCNNTorso(dense=self.dense, dtype=dt, name="torso")(x)

    def _head(self, x: jax.Array, dt) -> jax.Array:
        if self.dueling:
            return DuelingHead(self.num_actions, dtype=dt, name="head")(x)
        return nn.Dense(self.num_actions, dtype=dt,
                        name="head")(x).astype(jnp.float32)

    @nn.compact
    def __call__(self, obs: jax.Array, state: LSTMState
                 ) -> tuple[jax.Array, LSTMState]:
        """obs: [B, T, ...] -> (q: [B, T, A] float32, final_state)."""
        dt = dtype_of(self.compute_dtype)
        b, t = obs.shape[:2]
        # named scopes (metadata only): device time by part of the net,
        # forward and backward (learner.lstm_scan_share)
        with jax.named_scope("r2d2.torso"):
            feats = self._torso(obs.reshape(b * t, *obs.shape[2:]), dt)
            feats = feats.reshape(b, t, -1).swapaxes(0, 1)  # [T, B, F]
        with jax.named_scope("r2d2.lstm_scan"):
            scan_cell = nn.scan(
                nn.OptimizedLSTMCell,
                variable_broadcast="params", split_rngs={"params": False},
                in_axes=0, out_axes=0,
            )(self.lstm_size, dtype=dt, name="lstm")
            state = tuple(s.astype(dt) for s in state)
            final_state, ys = scan_cell(state, feats)  # ys: [T, B, H]
        with jax.named_scope("r2d2.head"):
            q = self._head(ys.swapaxes(0, 1).reshape(b * t, -1), dt)
            q = q.reshape(b, t, self.num_actions)
        return q, tuple(s.astype(jnp.float32) for s in final_state)

    @nn.compact
    def step(self, obs: jax.Array, state: LSTMState
             ) -> tuple[jax.Array, LSTMState]:
        """obs: [B, ...] single timestep for acting."""
        dt = dtype_of(self.compute_dtype)
        feats = self._torso(obs, dt)
        cell = nn.OptimizedLSTMCell(self.lstm_size, dtype=dt, name="lstm")
        state = tuple(s.astype(dt) for s in state)
        new_state, y = cell(state, feats)
        q = self._head(y, dt)
        return q, tuple(s.astype(jnp.float32) for s in new_state)

    def initial_state(self, batch: int) -> LSTMState:
        z = jnp.zeros((batch, self.lstm_size), jnp.float32)
        return (z, z)
