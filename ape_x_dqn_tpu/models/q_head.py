"""A decoder net's Q-head read one column a token.

A decoder net's Q-values are the columns of one matrix over the head's
input x (the final normed hidden state [B, T, hidden], in the compute
dtype): Q(s_t, a) = x_t . lm_head[:, a], `lm_head` float32 [hidden, A],
cast to the compute dtype at use, float32 accumulation. The whole slice
[B, T, A] is one matmul and is what an argmax or a max over the actions
needs: the inference server, the actors, the loss's double-Q choice.
The sequence loss reads ONE column a token of the target net (the
bootstrap at a*) and of the online net at the action taken, the only
place a gradient enters: `q_at`. The same rounded weights as the
matmul's, the same float32 accumulation, in another order of the sum
over `hidden`. Its gradient: d x is g x the gathered column, d lm_head a
scatter-add of g x x_t into the columns `ids` (ids repeat inside a batch
and the adds accumulate, in float32), where the matmul's backward pass
multiplied a [tokens, A] array of zeros twice.

A net offers the read by mixing `ColumnHead` in and handing the head's
input back beside Q (`apply_with_stats`'s `stats["head_input"]`);
runtime/family.decoder_q_family then has the loss read by column
(ops/losses.column_read). AfmoeQNet and SmallThinkerQNet do (ISSUE 49).
THE VOCABULARY HELD IS NOT WHOLE LANE TILES OF 128 THERE (25,024 and
18,992): XLA:TPU keeps such a float32 [hidden, A] parameter transposed
by itself, so the scatter's result is the gradient as Adam reads it.
At A % 128 == 0 (Ouro's 49,152) it keeps the parameter row-major and
would rather flip `lm_head`, its two moments and the target's copy to
the scatter's layout for the whole train loop (+1.5 GiB of temp there)
than transpose one gradient a step: such a net needs the gradient
pinned to the parameter's layout before it offers this (ROADMAP S5.9).
A NET WHOSE HEAD IS ITS EMBEDDING (Lfm2MoeQNet; `q_at(..., by_row=True)`
over E [A, hidden]) has that by construction: Q(s_t, a) = x_t . E[a], the
gathered "columns" are E's own rows and the scatter-add lands in the
parameter's own layout, to which autodiff adds the lookup's
scatter-add (the matrix has two uses and one gradient).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# around the column reads, so that a traced run says what they cost
# beside the nets' `*.head` scopes (op metadata only)
COLUMNS_SCOPE = "head.columns"


def _rounded_rows(table: jax.Array, ids: jax.Array, dt) -> jax.Array:
    """Rows `ids` of `table` [A, hidden] (already in the compute dtype
    `dt`) as the matmul reads them, float32 [B, T, hidden]."""
    f32 = jnp.float32
    rows = jnp.take(table, ids, axis=0).astype(f32)
    if dt != f32:
        # held whatever XLA fuses (ouro_q._held says why)
        info = jnp.finfo(dt)
        rows = jax.lax.reduce_precision(rows, info.nexp, info.nmant)
    return rows


def _reader(by_row: bool):
    """-> the read over a head kept [hidden, A] (`by_row` False: an
    untied `lm_head`) or [A, hidden] (`by_row` True: a head that IS the
    embedding, models/lfm2_moe_q.py). The first gathers whole rows of
    the TRANSPOSED, ROUNDED matrix: a column of [hidden, A] is one
    value a tile, and the transposed compute-dtype copy is the operand
    XLA:TPU lays out for the head's matmul anyway; gathered from the
    float32 matrix they cost three whole-matrix transposes a step
    (PERF.md section 6, PR 48). The second's rows are the parameter's
    own: no transpose in the gather and none on the scatter's result."""

    def rows_of(x, head, ids):
        table = head.astype(x.dtype)
        return _rounded_rows(table if by_row else table.T, ids, x.dtype)

    @jax.custom_vjp
    def read(x, head, ids):
        return jnp.sum(x.astype(jnp.float32) * rows_of(x, head, ids),
                       axis=-1)

    def fwd(x, head, ids):
        return read(x, head, ids), (x, head, ids)

    def bwd(res, g):
        """d x = g x the column; d head = the scatter-add of g x x_t
        into the columns `ids`, summed in float32 over an id's repeats.
        Written out because the forward pass gathers ROUNDED columns:
        autodiff's transpose of that would add an id's repeats in the
        compute dtype."""
        x, head, ids = res
        f32 = jnp.float32
        g = g[..., None]
        d_x = (g * rows_of(x, head, ids)).astype(x.dtype)
        rows = (g * x.astype(f32)).reshape(-1, x.shape[-1])
        shape = head.shape if by_row else head.shape[::-1]
        d_head = jnp.zeros(shape, f32).at[ids.reshape(-1)].add(rows)
        return (d_x, (d_head if by_row else d_head.T).astype(head.dtype),
                None)

    read.defvjp(fwd, bwd)
    return read


_q_at, _q_at_rows = _reader(False), _reader(True)


def q_at(x: jax.Array, lm_head: jax.Array, ids: jax.Array,
         by_row: bool = False) -> jax.Array:
    """x [B, T, hidden], lm_head float32 [hidden, A] (`by_row`: [A,
    hidden], the embedding of a tied head), ids [B, T] -> Q(s_t, ids_t)
    [B, T] float32: the head's matmul at `ids` without the other
    columns."""
    with jax.named_scope(COLUMNS_SCOPE):
        return (_q_at_rows if by_row else _q_at)(
            x, lm_head, ids.astype(jnp.int32))


class ColumnHead:
    """What the family's loss asks of a decoder net that offers the
    column read, beside `apply`: `head_at` over the net's own
    `lm_head`."""

    def head_at(self, params: dict, x: jax.Array,
                ids: jax.Array) -> jax.Array:
        """The head's input x [B, T, hidden] (`stats["head_input"]`),
        ids [B, T] -> Q(s_t, ids_t) [B, T] float32."""
        return q_at(x, params["lm_head"], ids)
