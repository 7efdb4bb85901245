"""Plain reference: dueling Nature-CNN forward and the n-step
double-DQN Huber loss, in float32 `jax.numpy`, written from the papers.

- Torso: Mnih et al. 2015 (Nature 518): conv 32x8x8 stride 4, conv
  64x4x4 stride 2, conv 64x3x3 stride 1, dense 512, ReLU after each,
  VALID padding, uint8 pixels scaled to [0, 1].
- Heads: Wang et al. 2016 (arXiv:1511.06581) eq. 9:
  Q = V + A - mean_a(A).
- Loss: Horgan et al. 2018 (arXiv:1803.00933) section 3 / van Hasselt
  et al. 2016: y = R_n + gamma^n * Q_target(s', argmax_a Q_online(s', a)),
  Huber(Q_online(s, a) - y) weighted by importance-sampling weights
  (Schaul et al. 2016), mean over the batch. New priority
  p = (|delta| + eps)^alpha.

No kernels, no cache, no batching tricks, nothing imported from the
system under test. Parameters arrive as plain arrays (`Params`); the
harness maps the system's pytree onto them. On a TPU a float32 matmul
or conv runs in lower precision unless asked otherwise, so every
entry point runs under `jax.default_matmul_precision("highest")`.

Departure from the papers: none in the mathematics; rewards and
discounts arrive already accumulated over n steps (R_n, gamma^n * (1 -
terminal)), which is how Ape-X actors ship them.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp


class Params(NamedTuple):
    conv_kernels: Sequence[jax.Array]   # each [k, k, c_in, c_out] (HWIO)
    conv_biases: Sequence[jax.Array]    # each [c_out]
    conv_strides: Sequence[int]
    dense_kernel: jax.Array             # [h*w*c, dense]
    dense_bias: jax.Array
    value_kernel: jax.Array             # [dense, 1]
    value_bias: jax.Array
    advantage_kernel: jax.Array         # [dense, num_actions]
    advantage_bias: jax.Array


def q_values(p: Params, obs: jax.Array) -> jax.Array:
    """obs [B, H, W, stack] uint8 -> Q [B, num_actions] float32."""
    with jax.default_matmul_precision("highest"):
        x = obs.astype(jnp.float32) / 255.0
        for w, b, s in zip(p.conv_kernels, p.conv_biases, p.conv_strides):
            x = jax.lax.conv_general_dilated(
                x, w.astype(jnp.float32), window_strides=(s, s),
                padding="VALID",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=jax.lax.Precision.HIGHEST)
            x = jnp.maximum(x + b.astype(jnp.float32), 0.0)
        x = x.reshape(x.shape[0], -1)
        x = jnp.maximum(x @ p.dense_kernel + p.dense_bias, 0.0)
        v = x @ p.value_kernel + p.value_bias
        a = x @ p.advantage_kernel + p.advantage_bias
        return v + a - a.mean(axis=-1, keepdims=True)


def huber(x: jax.Array, delta: float) -> jax.Array:
    ax = jnp.abs(x)
    return jnp.where(ax <= delta, 0.5 * x * x,
                     delta * (ax - 0.5 * delta))


def double_dqn_loss(online: Params, target: Params, obs, actions,
                    returns, next_obs, discounts, weights,
                    huber_delta: float = 1.0):
    """-> (scalar loss, |TD| per sample [B])."""
    q_s = q_values(online, obs)
    q_next_online = q_values(online, next_obs)
    q_next_target = q_values(target, next_obs)
    rows = jnp.arange(q_s.shape[0])
    a_star = jnp.argmax(q_next_online, axis=-1)
    y = returns + discounts * q_next_target[rows, a_star]
    td = q_s[rows, actions.astype(jnp.int32)] - y
    loss = jnp.mean(weights * huber(td, huber_delta))
    return loss, jnp.abs(td)


def new_priority(td_abs: jax.Array, alpha: float, eps: float):
    return (td_abs + eps) ** alpha
