"""Causal attention over blocks of queries and keys, for sequences
whose score matrix cannot exist (models/afmoe_q.py: 32 heads x 6,144
queries x 8,192 keys of float32 are 6.4 GB a layer and sequence).

    blockwise_attention(q, k, v, cache, window=...) -> out [B, T, Hq, d]

`q` [B, T, Hq, d], `k`/`v` [B, T, Hkv, d] are the new positions; `cache`
= (k_c, v_c) [B, C, Hkv, d] the C positions before them, or None. Query
t sits at key index C + t and sees key s iff s <= C + t and, with a
`window`, (C + t) - s < window (the query itself counts, so `window`
keys). Grouped queries: head j reads key-value head j // (Hq / Hkv).
score = q . k / sqrt(d), softmax in float32.

One function for every mask a decoder here uses; what the mask buys is
in the loop bounds: a block of queries visits only the blocks of keys
its mask admits (a causal block none after its diagonal, a windowed one
none before its window), so a sliding layer at 6,144 queries over 8,191
keys does a third of a full layer's work. Online softmax (running
maximum and sum, one [.., block_q, block_k] tile of scores alive at a
time), its own backward pass (`jax.custom_vjp`: the tiles are recomputed
from the saved log-sum-exp, so nothing of size [T, S] is kept for it
either), plain `jax.numpy` in two nested `fori_loop`s whose bounds
follow the query block - the same code on the CPU and on the chip.

WITH `about_mean`, BELOW FLOAT32, THE KEYS AND THE VALUES GO IN LESS
THEIR MEAN over the call's positions (as far as it can be taken off
exactly: `_about_its_mean`) and the values' is added to the output:
the same function, since a row's
weights add up to 1 and its scores may move by one number, and rows
that share one large common vector no longer make the q and k
projections' gradients a small difference of large numbers.
models/smallthinker_q.py asks for it (no q/k norms, no embedding scale);
models/afmoe_q.py does not, and its program is what it was. float32
compute is untouched either way.

WITH `recompute_delta` THE BACKWARD PASS TAKES A ROW'S DELTA FROM ITS
OWN WEIGHTS. ds = p (dp - delta) needs delta = sum_j p_j dp_j, the row's
mean of dp under its weights. The plain pass reads it off the forward
pass's output (delta = out . d_out, the usual saving: no second visit of
the row's key blocks), which is the same number only as far as the
forward pass's weights - exp(s - running max), rescaled block by block,
over their float32 total - and the backward pass's exp(s - lse) are the
same weights. On the v5e they are not to the last bits, a row of ds then
sums to e = sum_j (p_j - p_fwd_j) dp_j and not to 0, and e multiplies
whatever the keys share (dq) and is itself multiplied by whatever the
values share (dp). In a net whose rows are one common vector plus a
little of the token that is most of the q and k projections' gradient
error (PERF.md section 6, PRs 39 and 41: on the chip 3.3-4.9 times
bfloat16's own error on the worst such leaf where the autodiff of a
materialised softmax reads 2.6, on the CPU no difference). With the
argument a query block first walks its key blocks once for delta_i =
sum_j p_ij dp_ij in float32, from the very p and dp the second walk
recomputes, so every row of ds sums to zero as exactly as autodiff's
would: two more tile products and one more exp a tile of the backward
pass (7 products for 5), nothing more kept (the output is no residual
then). models/ouro_q.py asks for it, and not for `about_mean`, which
takes away what e multiplies and not e - and on that net's rows left
some leaves worse than it found them (same place).

THE CACHE CARRIES NO GRADIENT: `cache` enters under `stop_gradient`
(ops/losses.make_r2d2_loss stops the prefix state's anyway), and the
backward pass skips the key blocks that lie wholly inside it.

What it reaches on a v5e, beside jax's splash-attention Pallas kernel
at the same shapes, is in PERF.md (section 6, PR 32).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

BLOCK_Q = 512
BLOCK_K = 512
_NEG = -1e30     # a masked score: finite, so an all-masked tile gives 0, not NaN


class _Geometry(NamedTuple):
    """Static shape of one call, in key-index space (keys are
    [padding | cache | new])."""
    pad: int            # zero keys in front, so that S is whole blocks
    first: int          # key index of query 0 (= pad + C)
    window: int | None
    block_q: int
    block_k: int
    # the backward pass takes a row's delta from its own weights, in a
    # first pass over the row's key blocks (`blockwise_attention`)
    recompute_delta: bool = False


def _divisor(n: int, target: int) -> int:
    """The largest divisor of n that is <= target."""
    return max(d for d in range(1, min(n, target) + 1) if n % d == 0)


def _bounds(geo: _Geometry, i):
    """Query block i -> [lo, hi): the key blocks its mask admits."""
    top = geo.first + (i + 1) * geo.block_q - 1          # last row's own key
    low = geo.pad
    if geo.window is not None:
        low = jnp.maximum(geo.pad,
                          geo.first + i * geo.block_q - geo.window + 1)
    return low // geo.block_k, top // geo.block_k + 1


def _visible(geo: _Geometry, i, j):
    """[block_q, block_k] bool: which of tile (i, j)'s pairs the mask
    admits."""
    rows = geo.first + i * geo.block_q + jnp.arange(geo.block_q)[:, None]
    cols = j * geo.block_k + jnp.arange(geo.block_k)[None, :]
    vis = (cols <= rows) & (cols >= geo.pad)
    if geo.window is not None:
        vis &= rows - cols < geo.window
    return vis


def _tile(x, index, size, axis):
    return jax.lax.dynamic_slice_in_dim(x, index * size, size, axis)


def _scores(geo: _Geometry, qi, kj, i, j):
    """-> (scaled, masked scores [B, KV, G, block_q, block_k] float32,
    the mask)."""
    s = jnp.einsum("bkgtd,bksd->bkgts", qi, kj,
                   preferred_element_type=jnp.float32)
    vis = _visible(geo, i, j)
    return jnp.where(vis, s * (qi.shape[-1] ** -0.5), _NEG), vis


def _forward(geo: _Geometry, q, k, v):
    """q [B, KV, G, T, d]; k, v [B, KV, S, d] -> (out [B, KV, G, T, d]
    and log-sum-exp [B, KV, G, T], both float32)."""
    b, kv, g, t, d = q.shape
    bq, bk = geo.block_q, geo.block_k

    def per_query_block(i, carry):
        out, lse = carry
        qi = _tile(q, i, bq, 3)

        def per_key_block(j, c):
            acc, m, total = c
            s, vis = _scores(geo, qi, _tile(k, j, bk, 2), i, j)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.where(vis, jnp.exp(s - m_new[..., None]), 0.0)
            alpha = jnp.exp(m - m_new)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgts,bksd->bkgtd", p.astype(v.dtype), _tile(v, j, bk, 2),
                preferred_element_type=jnp.float32)
            return acc, m_new, total * alpha + p.sum(axis=-1)

        lo, hi = _bounds(geo, i)
        acc, m, total = jax.lax.fori_loop(lo, hi, per_key_block, (
            jnp.zeros((b, kv, g, bq, d), jnp.float32),
            jnp.full((b, kv, g, bq), _NEG, jnp.float32),
            jnp.zeros((b, kv, g, bq), jnp.float32)))
        out = jax.lax.dynamic_update_slice_in_dim(
            out, acc / total[..., None], i * bq, 3)
        lse = jax.lax.dynamic_update_slice_in_dim(
            lse, m + jnp.log(total), i * bq, 3)
        return out, lse

    return jax.lax.fori_loop(0, t // bq, per_query_block, (
        jnp.zeros(q.shape, jnp.float32),
        jnp.zeros((b, kv, g, t), jnp.float32)))


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _attend(geo: _Geometry, q, k, v):
    """-> the output in float32 (the caller rounds it once, after the
    values' mean is back in it)."""
    return _forward(geo, q, k, v)[0]


def _attend_fwd(geo, q, k, v):
    # the backward pass keeps the output in float32: each row of ds is
    # p (dp - delta) with delta = out . d_out, and where the values
    # share a large common component (random weights do) dp - delta is
    # a small difference of large numbers; an output rounded to
    # bfloat16 puts the same relative error into a whole row of ds,
    # which then sums coherently into the gradients of the q and k
    # projections and head norms (on the v5e, held to a float32
    # reference: 5 to 20 times bfloat16's own error on those leaves)
    out, lse = _forward(geo, q, k, v)
    return out, (q, k, v, None if geo.recompute_delta else out, lse)


def _attend_bwd(geo, res, d_out):
    """The cotangents of q, k, v; keys below `geo.first` (padding and
    cache) get none."""
    q, k, v, out, lse = res
    d_out = d_out.astype(q.dtype)        # as the rounded output's would be
    b, kv, g, t, d = q.shape
    bq, bk = geo.block_q, geo.block_k
    scale = d ** -0.5
    if not geo.recompute_delta:
        # [B, KV, G, T]
        delta = jnp.sum(out * d_out.astype(jnp.float32), axis=-1)
    with_grad_from = geo.first // bk     # key blocks before it: all cache

    def per_query_block(i, carry):
        dq, dk, dv = carry
        qi, doi = _tile(q, i, bq, 3), _tile(d_out, i, bq, 3)
        lse_i = _tile(lse, i, bq, 3)
        if not geo.recompute_delta:
            delta_i = _tile(delta, i, bq, 3)

        def weights(j):
            """Tile (i, j)'s softmax weights and d_out . v, float32."""
            kj, vj = _tile(k, j, bk, 2), _tile(v, j, bk, 2)
            s, vis = _scores(geo, qi, kj, i, j)
            p = jnp.where(vis, jnp.exp(s - lse_i[..., None]), 0.0)
            dp = jnp.einsum("bkgtd,bksd->bkgts", doi, vj,
                            preferred_element_type=jnp.float32)
            return p, dp, kj

        def d_scores(j):
            p, dp, kj = weights(j)
            ds = (p * (dp - delta_i[..., None]) * scale).astype(q.dtype)
            return p.astype(q.dtype), ds, kj

        def add_dq(dq_i, ds, kj):
            return dq_i + jnp.einsum("bkgts,bksd->bkgtd", ds, kj,
                                     preferred_element_type=jnp.float32)

        def cached_key_block(j, dq_i):
            _, ds, kj = d_scores(j)
            return add_dq(dq_i, ds, kj)

        def per_key_block(j, c):
            dq_i, dk, dv = c
            p, ds, kj = d_scores(j)
            dk_j = jnp.einsum("bkgts,bkgtd->bksd", ds, qi,
                              preferred_element_type=jnp.float32)
            dv_j = jnp.einsum("bkgts,bkgtd->bksd", p, doi,
                              preferred_element_type=jnp.float32)
            dk = jax.lax.dynamic_update_slice_in_dim(
                dk, _tile(dk, j, bk, 2) + dk_j, j * bk, 2)
            dv = jax.lax.dynamic_update_slice_in_dim(
                dv, _tile(dv, j, bk, 2) + dv_j, j * bk, 2)
            return add_dq(dq_i, ds, kj), dk, dv

        lo, hi = _bounds(geo, i)
        if geo.recompute_delta:
            def row_delta(j, total):
                p, dp, _ = weights(j)
                return total + (p * dp).sum(axis=-1)

            delta_i = jax.lax.fori_loop(
                lo, hi, row_delta, jnp.zeros((b, kv, g, bq), jnp.float32))
        split = jnp.clip(with_grad_from, lo, hi)
        dq_i = jax.lax.fori_loop(
            lo, split, cached_key_block,
            jnp.zeros((b, kv, g, bq, d), jnp.float32))
        dq_i, dk, dv = jax.lax.fori_loop(split, hi, per_key_block,
                                         (dq_i, dk, dv))
        dq = jax.lax.dynamic_update_slice_in_dim(
            dq, dq_i.astype(q.dtype), i * bq, 3)
        return dq, dk, dv

    dq, dk, dv = jax.lax.fori_loop(0, t // bq, per_query_block, (
        jnp.zeros_like(q), jnp.zeros(k.shape, jnp.float32),
        jnp.zeros(v.shape, jnp.float32)))
    new = (jnp.arange(k.shape[2]) >= geo.first)[:, None]
    return (dq, jnp.where(new, dk, 0.0).astype(k.dtype),
            jnp.where(new, dv, 0.0).astype(v.dtype))


_attend.defvjp(_attend_fwd, _attend_bwd)


MEAN_TAKEN_FROM = 4.0   # |mean| / spread of a coordinate, see below


def _about_its_mean(x):
    """x [B, S, KV, d] -> (x less what is taken off each coordinate, in
    x's dtype; what was taken off [B, KV, d] float32, or None where
    nothing was). ATTENTION DOES NOT SEE A VECTOR ADDED TO EVERY KEY
    (each row's scores move by one number, q . m, which the softmax
    drops) AND GIVES BACK A VECTOR ADDED TO EVERY VALUE (a row's weights
    add up to 1), so below float32 the passes run on keys and values
    less m and the values' m is added to the output: the same function,
    m held as a constant under `stop_gradient` because the output does
    not depend on it.

    What it buys: a decoder with no q/k norms and no embedding scale has
    hidden states that are one common vector plus a little of the token,
    so keys, values and the output's cotangent are near equal along a
    sequence and the q and k projections' gradients are small
    differences of large numbers. The backward pass's ds = p (dp - delta)
    sums to zero over a row only as far as the forward pass's rounded
    weights and the backward pass's recomputed ones agree, and what is
    left of the row sum is multiplied by the keys' common vector in dq
    and carried by the values' common vector in delta = out . d_out: on
    the v5e the sliding layers' q and k projections read 5-20 units of
    bfloat16's own error after a window of Adam steps (PERF.md section
    6, PR 39). Without a common vector there is nothing for either to
    multiply.

    WHAT IS TAKEN OFF IS CHOSEN SO THAT NOTHING IS ROUNDED TWICE: m is
    the positions' mean ROUNDED TO x's DTYPE, and only in the
    coordinates where it is at least MEAN_TAKEN_FROM times the spread
    about it (elsewhere 0). x and m are then two numbers of one format
    within a factor of two of each other, whose difference that format
    holds exactly (Sterbenz), so x - m is x to the last bit wherever the
    common vector matters, and x itself where it does not. A mean taken
    off in full is rounded again on the way back to x's dtype, and the
    MEAN of those roundings over the keys is one error added to every
    row's output - it does not average out over a sequence as a
    rounding does: the gradient's common error then read 0.4 to 2.3 of
    the unit over fourteen seeds where this form reads what the plain
    kernel does (PERF.md, same place). float32 compute: x as it is, and
    no op."""
    if x.dtype == jnp.float32:
        return x, None
    x32 = x.astype(jnp.float32)
    mean = x32.mean(axis=1)
    spread = jnp.sqrt(jnp.square(x32 - mean[:, None]).mean(axis=1))
    held = mean.astype(x.dtype).astype(jnp.float32)
    taken = jax.lax.stop_gradient(jnp.where(
        jnp.abs(held) >= MEAN_TAKEN_FROM * spread, held, 0.0))
    return (x32 - taken[:, None]).astype(x.dtype), taken


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        cache: tuple | None = None, *,
                        window: int | None = None,
                        block_q: int = BLOCK_Q,
                        block_k: int = BLOCK_K,
                        about_mean: bool = False,
                        recompute_delta: bool = False) -> jax.Array:
    """See the module docstring. `block_q` is cut to a divisor of T;
    the keys are padded in front to whole blocks of `block_k`.
    `about_mean`: keys and values go in less their mean
    (`_about_its_mean`; a net whose rows share one large vector asks
    for it). `recompute_delta`: the backward pass walks a row's key
    blocks twice, first for its delta (the other way to serve such a
    net: the rows of ds then sum to zero whatever they share)."""
    b, t, heads, d = q.shape
    kv = k.shape[2]
    if cache is not None:
        k = jnp.concatenate(
            [jax.lax.stop_gradient(cache[0]).astype(k.dtype), k], axis=1)
        v = jnp.concatenate(
            [jax.lax.stop_gradient(cache[1]).astype(v.dtype), v], axis=1)
    v_mean = None
    if about_mean:
        k, _ = _about_its_mean(k)
        v, v_mean = _about_its_mean(v)
    s = k.shape[1]
    bk = min(block_k, s)
    pad = -s % bk
    geo = _Geometry(pad=pad, first=pad + s - t, window=window,
                    block_q=_divisor(t, block_q), block_k=bk,
                    recompute_delta=recompute_delta)
    front = ((0, 0), (pad, 0), (0, 0), (0, 0))
    k, v = (jnp.pad(a, front).transpose(0, 2, 1, 3) for a in (k, v))
    q = q.reshape(b, t, kv, heads // kv, d).transpose(0, 2, 3, 1, 4)
    out = _attend(geo, q, k, v)             # [B, KV, G, T, d] float32
    if v_mean is not None:
        out = out + v_mean[:, :, None, None]
    out = out.astype(q.dtype)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, heads, d)
