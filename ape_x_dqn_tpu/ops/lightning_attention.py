"""Linear attention with ONE SCALAR DECAY A HEAD (Lightning
Attention-2, arXiv:2401.04658, as MiniCPM-SALA's `lightning-attn`
layers use it), the second recurrent op beside
ops/chunked_delta_rule.py and no generalisation of it: there is no
solve and no per-channel decay, only

    S_t = lambda_h S_{t-1} + k_t^T v_t          S float32 [d_k, d_v]
    o_t = q_t S_t * scale

per head h, `lambda_h = exp(-slope_h)`. Two forms of the same function:

- `step`: one position, the recurrence as written (the inference
  server's decode step: models/minicpm_sala_q.py `extend` at n = 1).
- `chunked`: T positions `CHUNK` at a time, a `jax.lax.scan` over the
  chunks that carries S. Inside a chunk `(Q K^T * D) V` with `D_ij =
  exp(G_i - G_j)` for i >= j, across chunks `exp(G_i) Q S` and
  `S <- exp(G_C) S + (exp(G_C - G_i) K)^T V`, where `G_i` is the log
  decay summed over the chunk's positions up to and including i.
  Every exponent is formed as a DIFFERENCE before the exponential, so
  nothing overflows however long the chunk. `valid` [B, T] marks the
  positions that count: one that does not neither decays the state nor
  adds to it (`G` stands still, its k is zero), which is how a ragged
  last chunk of a prefill and the padding to a whole chunk leave the
  state where the last real position left it; its own output row is
  garbage the caller never reads.

Everything here is float32 at `Precision.HIGHEST`: q, k and v arrive
already rounded to the compute dtype where the net holds them, the
state, the decays and the products with either are exact to float32.
At heads of 128 that costs 7 d^2 FLOP a token, head and layer at six
passes, a few per cent of the projections around it (PERF.md section
6, PR 55).

Scopes are the caller's (`sala.lightning.state`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# positions a chunk: 128 fills the MXU's rows at heads of 128; 64 read
# the same on the chip within the spread (PERF.md section 6, PR 55)
CHUNK = 128

_HI = jax.lax.Precision.HIGHEST


def slopes(num_heads: int) -> jax.Array:
    """-> [heads] float32, slope_h = 2^(-8 (h + 1) / heads): lambda_h =
    exp(-slope_h), the same in every layer."""
    h = jnp.arange(1, num_heads + 1, dtype=jnp.float32)
    return jnp.exp2(-8.0 * h / num_heads)


def step(q: jax.Array, k: jax.Array, v: jax.Array, state: jax.Array,
         slope: jax.Array, scale: float) -> tuple[jax.Array, jax.Array]:
    """q, k, v [B, H, d] of ONE position, state [B, H, d, d] float32 ->
    (o [B, H, d] float32, the state after it)."""
    f32 = jnp.float32
    lam = jnp.exp(-slope)[None, :, None, None]
    state = lam * state + (k.astype(f32)[..., :, None]
                           * v.astype(f32)[..., None, :])
    o = jnp.einsum("bhd,bhde->bhe", q.astype(f32), state, precision=_HI)
    return o * scale, state


def chunked(q: jax.Array, k: jax.Array, v: jax.Array, state: jax.Array,
            slope: jax.Array, scale: float, valid: jax.Array | None = None,
            chunk: int = CHUNK) -> tuple[jax.Array, jax.Array]:
    """q, k, v [B, T, H, d], state [B, H, d, d] float32, `valid` [B, T]
    bool (None: every position counts) -> (o [B, T, H, d] float32, the
    state after the last valid position)."""
    f32 = jnp.float32
    b, t, h, d = q.shape
    if valid is None:
        valid = jnp.ones((b, t), bool)
    c = min(chunk, t)
    pad = -t % c
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        valid = jnp.pad(valid, ((0, 0), (0, pad)))
    n = (t + pad) // c

    def chunks(a):      # [B, n c, H, d] -> [n, B, H, c, d]
        return a.reshape(b, n, c, h, d).transpose(1, 0, 3, 2, 4)

    counts = valid.astype(f32)
    k = k.astype(f32) * counts[..., None, None]
    causal = jnp.tril(jnp.ones((c, c), bool))
    # G_i = -slope * (valid positions of the chunk up to i), [n, B, H, c]
    g_all = (-jnp.cumsum(counts.reshape(b, n, c), axis=2)[:, :, None, :]
             * slope[None, None, :, None]).transpose(1, 0, 2, 3)

    def one(s, xs):
        qc, kc, vc, g = xs                 # [B, H, c, d], g [B, H, c]
        g_end = g[..., -1]                                 # [B, H]
        apart = jnp.where(causal, g[..., :, None] - g[..., None, :], 0.0)
        inside = jnp.where(causal, jnp.exp(apart), 0.0)    # [B, H, i, j]
        scores = jnp.einsum("bhid,bhjd->bhij", qc, kc, precision=_HI)
        o = jnp.einsum("bhij,bhje->bhie", scores * inside, vc,
                       precision=_HI)
        o = o + jnp.einsum("bhid,bhde->bhie",
                           qc * jnp.exp(g)[..., None], s, precision=_HI)
        k_left = kc * jnp.exp(g_end[..., None] - g)[..., None]
        s = (jnp.exp(g_end)[..., None, None] * s
             + jnp.einsum("bhjd,bhje->bhde", k_left, vc, precision=_HI))
        return s, o * scale

    state, o = jax.lax.scan(
        one, state, (chunks(q.astype(f32)), chunks(k),
                     chunks(v.astype(f32)), g_all))
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, n * c, h, d)
    return o[:, :t], state
