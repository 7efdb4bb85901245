"""Mamba-1's SELECTIVE SCAN (Gu & Dao, arXiv:2312.00752, as Jamba's
Mamba layers use it), the third recurrent op beside
ops/chunked_delta_rule.py (a matrix state a head, WY form) and
ops/lightning_attention.py (one scalar decay a head) and a
generalisation of neither: a DIAGONAL recurrence per channel with an
input-dependent step,

    h_t = exp(delta_t (x) A) * h_{t-1} + (delta_t * x_t) (x) B_t
    y_t = h_t C_t + D * x_t

per row, h float32 `[d_state, channels]`, A `[d_state, channels]` < 0,
delta_t, x_t `[channels]` (delta > 0: the softplus of the caller's
projection), B_t, C_t `[d_state]`. The decay differs per channel AND per
state coordinate AND per token, so no product of matrices computes a
chunk of it: what the other two ops do on the MXU is elementwise here.

THE STATE IS KEPT `[..., d_state, channels]`, channels last: d_state is
16 and a minor dimension of 16 fills an eighth of a TPU tile's 128
lanes (a pool of `[5120, 16]` float32 matrices would take eight times
its bytes on the device and in every pass over it).

Two entries over one definition (`_advance`, one position of every row):

- `step`: one position a row: a decode step on the rows the caller
  gathered from its slot pool.
- `chunked`: T positions a row from each row's own start state, ragged:
  `valid` [rows, T] marks the positions that count, and one that does
  not leaves h exactly as it was (its delta is made 0: the decay is
  exp(0) = 1 and the input 0 (x) B = 0); its own y is garbage the caller
  never reads. A loop over chunks of `CHUNK` positions carries h; inside
  a chunk the positions are walked in order, unrolled, so that the
  compiler may keep h out of HBM between them. Every exponent
  is delta * A <= 0: no exp(-cumsum) is ever formed and nothing
  overflows however long the sequence.

Everything is float32: x arrives already rounded to the compute dtype
where the net holds it; delta, the exponential, h and the products with
it are float32 as written (the VPU's). Scopes are the caller's
(`jamba.mamba.scan`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# positions a chunk of `chunked`: the loop's body is this many positions
# unrolled, h carried once a chunk. The one length read on the chip: a
# prefill dispatch of 8 x 2,048 tokens through 26 such layers and the
# rest of Jamba2's stack takes 0.84 s (PERF.md section 6, PR 57)
CHUNK = 16


def _advance(h: jax.Array, x: jax.Array, delta: jax.Array, a: jax.Array,
             b: jax.Array, c: jax.Array, d: jax.Array
             ) -> tuple[jax.Array, jax.Array]:
    """The definition, one position: h [R, N, D] float32, x, delta [R,
    D] float32, a [N, D], b, c [R, N], d [D] -> (y [R, D], h after)."""
    decay = jnp.exp(delta[:, None, :] * a)
    h = decay * h + (delta * x)[:, None, :] * b[:, :, None]
    return jnp.sum(h * c[:, :, None], axis=1) + d * x, h


def step(h: jax.Array, x: jax.Array, delta: jax.Array, a: jax.Array,
         b: jax.Array, c: jax.Array, d: jax.Array,
         valid: jax.Array | None = None) -> tuple[jax.Array, jax.Array]:
    """ONE position a row. h [R, N, D] float32, x, delta [R, D], a [N,
    D] (= -exp(A_log), transposed), b, c [R, N], d [D], `valid` [R] bool
    (a row that does not count keeps its h) -> (y [R, D] float32, the
    state after it)."""
    f32 = jnp.float32
    delta = delta.astype(f32)
    if valid is not None:
        delta = jnp.where(valid[:, None], delta, 0.0)
    return _advance(h.astype(f32), x.astype(f32), delta, a.astype(f32),
                    b.astype(f32), c.astype(f32), d.astype(f32))


def chunked(h: jax.Array, x: jax.Array, delta: jax.Array, a: jax.Array,
            b: jax.Array, c: jax.Array, d: jax.Array,
            valid: jax.Array | None = None
            ) -> tuple[jax.Array, jax.Array]:
    """T positions a row. h [R, N, D] float32 (each row's start state),
    x, delta [R, T, D], a [N, D], b, c [R, T, N], d [D], `valid` [R, T]
    bool -> (y [R, T, D] float32, h after each row's valid positions)."""
    f32 = jnp.float32
    r, t, width = x.shape
    a, d = a.astype(f32), d.astype(f32)
    chunk = min(CHUNK, t)
    count = -(-t // chunk)
    pad = count * chunk - t
    if valid is None:
        valid = jnp.ones((r, t), bool)
    # a padding position is one that does not count. The chunks are
    # SLICED out of the arrays as they arrived and y is written into one
    # carried buffer: a [chunks, chunk, ...] copy of x, delta and y each
    # was most of a prefill dispatch's temporary memory

    def padded(arr):
        return jnp.pad(arr, ((0, 0), (0, pad)) + ((0, 0),) * (arr.ndim - 2))

    x, delta, b, c, valid = (padded(arr) for arr in (x, delta, b, c, valid))

    def one_chunk(i, carry):
        h, y = carry

        def piece(arr):
            return jax.lax.dynamic_slice_in_dim(arr, i * chunk, chunk, 1)

        xs, bs, cs = (piece(arr).astype(f32) for arr in (x, b, c))
        ds = jnp.where(piece(valid)[..., None], piece(delta).astype(f32), 0.0)
        ys = []
        for j in range(chunk):
            y_j, h = _advance(h, xs[:, j], ds[:, j], a, bs[:, j], cs[:, j], d)
            ys.append(y_j)
        return h, jax.lax.dynamic_update_slice_in_dim(
            y, jnp.stack(ys, axis=1), i * chunk, 1)

    h, y = jax.lax.fori_loop(
        0, count, one_chunk,
        (h.astype(f32), jnp.zeros((r, count * chunk, width), f32)))
    return y[:, :t], h
