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
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# around the column reads, so that a traced run says what they cost
# beside the nets' `*.head` scopes (op metadata only)
COLUMNS_SCOPE = "head.columns"


def _columns(x: jax.Array, lm_head: jax.Array, ids: jax.Array) -> jax.Array:
    """The columns `ids` of `lm_head` as the matmul reads them (rounded
    to x's dtype), float32 [B, T, hidden]. Whole rows of the TRANSPOSED,
    ROUNDED matrix: a column of [hidden, A] is one value a tile, and the
    transposed compute-dtype copy is the operand XLA:TPU lays out for
    the head's matmul anyway; gathered from the float32 matrix they
    cost three whole-matrix transposes a step (PERF.md section 6, PR
    48)."""
    f32 = jnp.float32
    columns = jnp.take(lm_head.astype(x.dtype).T, ids, axis=0).astype(f32)
    if x.dtype != f32:
        # held whatever XLA fuses (ouro_q._held says why)
        info = jnp.finfo(x.dtype)
        columns = jax.lax.reduce_precision(columns, info.nexp, info.nmant)
    return columns


@jax.custom_vjp
def _q_at(x: jax.Array, lm_head: jax.Array, ids: jax.Array) -> jax.Array:
    return jnp.sum(x.astype(jnp.float32) * _columns(x, lm_head, ids),
                   axis=-1)


def _q_at_fwd(x, lm_head, ids):
    return _q_at(x, lm_head, ids), (x, lm_head, ids)


def _q_at_bwd(res, g):
    """d x = g x the column; d lm_head = the scatter-add of g x x_t into
    the columns `ids`, summed in float32 over an id's repeats. Written
    out because the forward pass gathers ROUNDED columns: autodiff's
    transpose of that would add an id's repeats in the compute dtype."""
    x, lm_head, ids = res
    f32 = jnp.float32
    g = g[..., None]
    d_x = (g * _columns(x, lm_head, ids)).astype(x.dtype)
    rows = (g * x.astype(f32)).reshape(-1, x.shape[-1])
    d_head = jnp.zeros(lm_head.shape[::-1], f32).at[ids.reshape(-1)].add(rows)
    return d_x, d_head.T.astype(lm_head.dtype), None


_q_at.defvjp(_q_at_fwd, _q_at_bwd)


def q_at(x: jax.Array, lm_head: jax.Array, ids: jax.Array) -> jax.Array:
    """x [B, T, hidden], lm_head float32 [hidden, A], ids [B, T] ->
    Q(s_t, ids_t) [B, T] float32: the head's matmul at `ids` without
    the other columns."""
    with jax.named_scope(COLUMNS_SCOPE):
        return _q_at(x, lm_head, ids.astype(jnp.int32))


class ColumnHead:
    """What the family's loss asks of a decoder net that offers the
    column read, beside `apply`: `head_at` over the net's own
    `lm_head`."""

    def head_at(self, params: dict, x: jax.Array,
                ids: jax.Array) -> jax.Array:
        """The head's input x [B, T, hidden] (`stats["head_input"]`),
        ids [B, T] -> Q(s_t, ids_t) [B, T] float32."""
        return q_at(x, params["lm_head"], ids)
