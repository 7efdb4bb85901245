"""The gated delta rule with a decay per channel (Kimi Delta Attention,
arXiv:2510.26692), over chunks of positions: the scan layer of
models/kimi_linear_q.py, and the first here that is not the LSTM.

    chunked_delta_rule(q, k, v, g, beta, state) -> (o, state)

`q`, `k`, `g` [B, T, H, dk], `v` [B, T, H, dv], `beta` [B, T, H];
`state` [B, H, dk, dv] float32, the matrix S the positions before left,
or None (zeros). Per head, one position at a time, the rule is

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t,                         alpha_t = exp(g_t), g_t <= 0

with alpha_t a vector over the dk key channels (one number a head is
Gated DeltaNet). -> `o` [B, T, H, dv] float32 and S after the last
position, float32. tests/test_chunked_delta_rule.py holds this file to
that recurrence, values and gradients.

THE CHUNKED FORM (WY / UT). Inside a chunk of C positions that starts
from S_0, with G_i the running sum of g up to and including position i
and u_i = beta_i (v_i - k_i^T Diag(alpha_i) S_{i-1}) (what position i
writes: S_i = Diag(alpha_i) S_{i-1} + k_i u_i^T), unrolling gives

    (I + A) U = beta (V - (K e^G) S_0),
        A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc),   j < i
    O   = (Q e^G) S_0 + B U,
        B_ij = sum_c q_ic k_jc exp(G_ic - G_jc),          j <= i
    S_C = Diag(e^{G_C}) S_0 + (K e^{G_C - G})^T U.

A is strictly lower triangular, so T = (I + A)^-1 exists and depends on
nothing but the chunk's own k, beta and g: `kda.scan.intra` makes A, B,
T, W = T (beta K e^G) and U_0 = T (beta V), `kda.scan.carry` is what is
left and sequential, U = U_0 - W S_0, O and S_C: three small matmuls a
chunk. A `lax.scan` over chunks carries S (float32) and the count of
chunks walked (`with_chunks`: what the family's `kda_chunks` reads).

THE DIFFERENCE IS FORMED BEFORE THE EXPONENTIAL. The factored form
(q e^{G_i}) . (k e^{-G_j}) would put A and B on the MXU, and it
overflows float32 once -G passes 88 inside a chunk: exp(A_log) up to 16
x softplus up to 0.1 a position x 64 positions is 102 (51 at this
file's chunk of 32 as initialised; training moves both). Here every
exponent is G_i - G_j with j <= i (<= 0; the other triangle is masked
BEFORE the exponential, so no infinity meets a zero in the backward
pass either), G_i, or G_C - G_i: all <= 0, so the worst is an underflow
to 0 of a term that is 0 to float32 anyway. What it costs is a [C, C,
dk] tile of elementwise work a chunk and head on the VPU where the
factored form has a matmul; re-basing inside sub-chunks (both factors
<= 1 across sub-chunks, the difference form on the diagonal ones) is
the known cure and the next `perf_opt`'s (PERF.md section 7).

THE SOLVE IS BLOCK FORWARD SUBSTITUTION, NOT A POWER SERIES. (I + A)^-1
= sum_n (-A)^n is finite (A is nilpotent) and is six matmuls by
doubling, but at random weights every key of a sequence is nearly one
vector, A is close to beta x the all-ones triangle, and the series'
terms reach 0.5^32 C(62, 31) = 1e8 with alternating signs where the
inverse's entries are below 1: float32 keeps nothing of it. Diagonal
blocks of `_BASE` = 8 are inverted by the doubled series (terms up to
C(6, 3) = 20: harmless), then pairs of blocks are joined,
[[P, 0], [R, Q]]^-1 = [[P^-1, 0], [-Q^-1 R P^-1, Q^-1]], twice to 32
(three times to 64): exact algebra, as stable as substitution, and all
of it batched matmuls.

PRECISION. The cumulative g, the solve, S and every product in the scan
are float32, the matmuls at `Precision.HIGHEST`: the products are 7 dk
dv FLOP a token and head, a hundredth of a decoder step's matmuls even
at six bfloat16 passes each, and what bounds the scan is the VPU tile
above and the chunk-to-chunk latency, not the MXU. The net rounds q, k
and v where IT holds them in its compute dtype (models/kimi_linear_q.py,
through `reduce_precision`) and passes float32; nothing is rounded here.

THE BACKWARD PASS is autodiff of the chunk under a `jax.checkpoint` of
its own: the scan keeps S at every chunk's start ([T / C, dk, dv] a
head: 201 MB a layer at 3,072 trained positions in chunks of 32, alive
only inside the block's own recomputation) and recomputes the chunk's
tiles; nothing of size [T, dk, dv] exists. A hand-written pass was not
tried.

A T OFF A CHUNK is padded behind with positions that leave the state as
it was (g = 0, beta = 0, and zeros): the server's `apply_window` sends
windows of any length.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Positions a chunk, CHOSEN ON THE CHIP (`kimi_linear_offline`, one v5e,
# my chip runs, PR 46; samples/s): 128 2.085, 64 2.736, 32 3.040. The
# [C, C, dk] tiles cost in proportion to C, the scan's sequencing to 1 / C;
# a fit of the three readings (step = 207 ms + 2.02 C + 1834 / C) puts the
# optimum at 30 and reads 354 ms at 16 against 329 at 32: 16 was not run.
CHUNK = 32
_BASE = 8        # diagonal blocks of this size are inverted by the series
_HI = jax.lax.Precision.HIGHEST
SCOPE, INTRA, CARRY = "kda.scan", "kda.scan.intra", "kda.scan.carry"


def _mm(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.matmul(a, b, precision=_HI)


def _diagonal_blocks(m: jax.Array, s: int, row: int = 0, col: int = 0,
                     of: int = 1) -> jax.Array:
    """m [..., C, C] -> [..., C / (of s), s, s]: of every diagonal block
    of `of` x `of` blocks of s, the block at (`row`, `col`); a mask and a
    sum, no gather."""
    n = m.shape[-1] // (of * s)
    lead = m.shape[:-2]
    m = m.reshape(*lead, n, of, s, n, of, s)[..., :, row, :, :, col, :]
    eye = jnp.eye(n, dtype=m.dtype)[:, None, :, None]
    return (m * eye).sum(axis=-2)


def _unit_lower_inverse(a: jax.Array) -> jax.Array:
    """a [..., C, C] strictly lower triangular, C = `_BASE` x a power of
    two (or below `_BASE`) -> (I + a)^-1 (module docstring)."""
    c = a.shape[-1]
    s = min(c, _BASE)
    x = -_diagonal_blocks(a, s)
    inv = jnp.eye(s, dtype=a.dtype) + x
    power, reach = x, 2
    while reach < s:                    # (I + x)(I + x^2)(I + x^4) ...
        power = _mm(power, power)
        inv = inv + _mm(inv, power)
        reach *= 2
    while s < c:
        below = _diagonal_blocks(a, s, 1, 0, of=2)       # [..., n, s, s]
        pairs = inv.reshape(*inv.shape[:-3], -1, 2, s, s)
        p, q = pairs[..., 0, :, :], pairs[..., 1, :, :]
        corner = -_mm(_mm(q, below), p)
        inv = jnp.concatenate([
            jnp.concatenate([p, jnp.zeros_like(p)], axis=-1),
            jnp.concatenate([corner, q], axis=-1)], axis=-2)
        s *= 2
    return inv[..., 0, :, :]


def _chunk(carry, xs):
    """One chunk: carry (S [B, H, dk, dv], chunks walked), xs the
    chunk's q, k, g [B, H, C, dk], v [B, H, C, dv], beta [B, H, C]."""
    s0, walked = carry
    q, k, v, g, beta = xs
    c = q.shape[-2]
    with jax.named_scope(INTRA):
        total = jnp.cumsum(g, axis=-2)                       # G [.., C, dk]
        at = jnp.arange(c)
        upto = (at[:, None] >= at[None, :])[..., None]       # j <= i
        apart = jnp.where(
            upto, total[..., :, None, :] - total[..., None, :, :], 0.0)
        decay = jnp.where(upto, jnp.exp(apart), 0.0)    # [.., C, C, dk]
        keys = decay * k[..., None, :, :]
        kk = (k[..., :, None, :] * keys).sum(axis=-1)        # [.., C, C]
        qk = (q[..., :, None, :] * keys).sum(axis=-1)
        below = (at[:, None] > at[None, :])
        inv = _unit_lower_inverse(
            beta[..., None] * jnp.where(below, kk, 0.0))
        grown = jnp.exp(total)                               # e^G
        w = _mm(inv, beta[..., None] * k * grown)            # [.., C, dk]
        u0 = _mm(inv, beta[..., None] * v)                   # [.., C, dv]
        q_in = q * grown
        k_out = k * jnp.exp(total[..., -1:, :] - total)
        kept = grown[..., -1, :, None]                       # e^{G_C}
    with jax.named_scope(CARRY):
        u = u0 - _mm(w, s0)
        o = _mm(q_in, s0) + _mm(qk, u)
        s1 = kept * s0 + _mm(jnp.swapaxes(k_out, -1, -2), u)
    return (s1, walked + 1), o


def chunked_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array,
                       g: jax.Array, beta: jax.Array,
                       state: jax.Array | None = None, *,
                       chunk: int = CHUNK, with_chunks: bool = False):
    """See the module docstring. `chunk`: positions a chunk (a power of
    two); `with_chunks`: also the chunks walked, int32."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    if chunk & (chunk - 1):
        raise ValueError(f"chunk={chunk}: a power of two")
    pad = -t % chunk
    n = (t + pad) // chunk

    def chunks(x):      # [B, T, H, ...] -> [N, B, H, C, ...]
        x = x.astype(jnp.float32)
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(b, n, chunk, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    if state is None:
        state = jnp.zeros((b, h, dk, dv), jnp.float32)
    with jax.named_scope(SCOPE):
        (state, walked), o = jax.lax.scan(
            jax.checkpoint(_chunk, prevent_cse=False),
            (state.astype(jnp.float32), jnp.int32(0)),
            tuple(chunks(x) for x in (q, k, v, g, beta)))
        # [N, B, H, C, dv] -> [B, T, H, dv]
        o = jnp.moveaxis(o, 0, 1).transpose(0, 1, 3, 2, 4).reshape(
            b, n * chunk, h, dv)[:, :t]
    return (o, state, walked) if with_chunks else (o, state)
