"""The causal depthwise filter two decoder nets share: Kimi-Linear's
conv4 ahead of KDA (models/kimi_linear_q.py, behind it a SiLU) and
LFM2's conv3 between its two gates (models/lfm2_moe_q.py, behind it
nothing). One filter a channel, no bias:

    out_t = sum_{j=0..K-1} w_j * x_{t - (K - 1) + j}

over `seen` = the K - 1 rows before the first new position, then the T
new rows. What a prefix leaves is the last K - 1 rows of `seen`, the
same size however long it was; `behind` puts them in front of the next
rows, zeros where there is no prefix, so a prefix shorter than K - 1
rows (a window of one token, an episode's first) reads zeros on its
left and leaves them there.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def short_conv(seen: jax.Array, w: jax.Array, t: int) -> jax.Array:
    """seen [B, K - 1 + T, width] (the tail, then the new rows), w [K,
    width] -> [B, T, width] float32: out_t = sum_j w_j x_{t - (K - 1) + j},
    one filter a channel."""
    w = w.astype(jnp.float32)
    return sum(w[j] * seen[:, j:j + t].astype(jnp.float32)
               for j in range(w.shape[0]))


def behind(tail, x: jax.Array, taps: int) -> jax.Array:
    """x [B, T, width] the new rows, `tail` [B, taps - 1, width] the
    rows before them or None (zeros) -> `short_conv`'s `seen`; its last
    taps - 1 rows are the tail the next call starts from."""
    if tail is None:
        tail = jnp.zeros((x.shape[0], taps - 1, x.shape[2]), x.dtype)
    return jnp.concatenate([tail.astype(x.dtype), x], axis=1)
