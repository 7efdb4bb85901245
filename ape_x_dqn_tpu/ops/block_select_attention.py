"""Attention over KEY BLOCKS CHOSEN BY THE DATA (InfLLM v2, MiniCPM4
report arXiv:2506.07900, as MiniCPM-SALA's `minicpm4` layers use it):
what ops/blockwise_attention.py cannot do, whose `_bounds` / `_visible`
admit blocks from static geometry alone.

For the query at position t (context T = t + 1) of one key-value head's
group of query heads:

- T <= `dense_len`: causal softmax attention over every position.
- T > `dense_len`: COMPRESSED KEYS `ck_j = mean(k[stride j : stride j +
  kernel])`, visible when `stride j + kernel <= T`; per query head
  `p = softmax_j(q . ck_j / sqrt(d))` over the visible j; the p of the
  group's heads summed; the SCORE of block m (`block` positions) the
  max of that sum over the windows that overlap it, `j in [per m -
  (kernel / stride - 1), per m + per - 1]`, `per = block / stride`; the
  first `init_blocks` blocks and the `window / block` blocks that end at
  the query's own score +inf; the `topk` best blocks (the forced ones
  counted in it) are attended: one softmax over their positions <= t,
  the same blocks for every head of the group.

The keys live in a POOL `[kv heads, positions, d]` (values alike,
compressed keys `[kv heads, positions / stride, d]`) in which a
sequence owns one contiguous range that starts at a block boundary
(`base`, in positions): the inference server's slot pool and the
learner's per-sequence cache are both that
(models/minicpm_sala_q.py). Block and window ids are the sequence's
own, 0 at `base`.

Entry points, all float32 softmax over compute-dtype keys and values:

- `write` / `compress`: new keys and values into the pool; the
  compressed keys their arrival completes, from what the pool holds.
- `select`: compressed keys + queries -> the chosen block ids
  (`-1` where fewer than `topk` blocks exist).
- `attend_gathered`: ONE query a sequence (a decode step) over a block
  LIST: the chosen blocks are gathered, `[rows, kv heads, K, block, d]`,
  and attended.
- `attend_range`: ONE query a sequence over its WHOLE range (a decode
  step of a net that selects nothing, models/jamba_q.py): a Pallas
  kernel walks each row's own tiles of the pools where they lie, up to
  that row's own context, with an online softmax - no gathered copy, no
  tile past the row's last (PERF.md section 6, PR 60).
- `attend_tiles`: MANY queries of one sequence (a prefill chunk, the
  learner's pass): key tiles of `tile` positions walked with an online
  softmax under the per-(query, block) mask, so a chunk's cost is dense
  attention's and no `[queries, blocks, block, d]` gather exists.

Scopes are the caller's (`sala.sparse.*`).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

NEG = -jnp.inf


class Sizes(NamedTuple):
    block: int        # positions a key block
    kernel: int       # positions a compressed key averages
    stride: int       # positions between two compressed keys
    init_blocks: int  # leading blocks always attended
    window: int       # positions behind the query always attended
    topk: int         # blocks attended in all
    dense_len: int    # contexts up to this attend densely

    @property
    def per(self) -> int:
        return self.block // self.stride

    @property
    def local_blocks(self) -> int:
        return self.window // self.block

    @property
    def reach(self) -> int:
        """Windows that start before a block and still overlap it."""
        return self.kernel // self.stride - 1

    def check(self) -> None:
        if (self.block % self.stride or self.kernel % self.stride
                or self.window % self.block
                or self.dense_len % self.block
                or self.topk < self.init_blocks + self.local_blocks):
            raise ValueError(
                f"sparse sizes {self}: stride must divide block and "
                f"kernel, block must divide window and dense_len, and "
                f"topk must hold the init and local blocks")


def _rows(pool: jax.Array) -> jax.Array:
    """pool [G, P, d] as rows [G P, d]: a scatter or gather along the
    positions of a [G, P, d] array makes XLA's TPU backend move P to
    the front and COPY the pool to get there, both ways, every step
    (0.8 GiB a copy at the cell's size: PERF.md section 6, PR 55); over
    plain rows it does neither."""
    return pool.reshape(-1, pool.shape[-1])


def write(pool: jax.Array, new: jax.Array, at: jax.Array,
          valid: jax.Array) -> jax.Array:
    """pool [G, P, d] <- new [B, n, G, d] at pool positions `at` [B, n]
    where `valid` [B, n]; the others are dropped."""
    g, p, d = pool.shape
    idx = jnp.where(valid[..., None], at[..., None] + jnp.arange(g) * p,
                    g * p)                                    # [B, n, G]
    rows = _rows(pool).at[idx.reshape(-1)].set(
        new.reshape(-1, d).astype(pool.dtype), mode="drop")
    return rows.reshape(pool.shape)


def compress(ck: jax.Array, kpool: jax.Array, base: jax.Array,
             before: jax.Array, after: jax.Array, n: int,
             sz: Sizes) -> jax.Array:
    """The compressed keys that the positions `before` .. `after` - 1
    ([B] each, at most n apart) completed, from the keys the pool holds
    (the new ones already written), into ck [G, P / stride, d]. A mean
    in float32, kept in the pool's dtype."""
    g, p, d = kpool.shape
    windows = ck.shape[1]
    count = -(-n // sz.stride)
    first = jnp.maximum((before - sz.kernel) // sz.stride + 1, 0)
    j = first[:, None] + jnp.arange(count)                    # [B, c]
    done = j * sz.stride + sz.kernel <= after[:, None]
    at = jnp.minimum(base[:, None, None] + j[:, :, None] * sz.stride
                     + jnp.arange(sz.kernel), p - 1)          # [B, c, kernel]
    head = jnp.arange(g)[:, None, None, None]
    keys = _rows(kpool)[head * p + at]                        # [G,B,c,kernel,d]
    mean = keys.astype(jnp.float32).mean(axis=3)
    idx = jnp.where(done, head[..., 0] * windows
                    + base[:, None] // sz.stride + j, g * windows)
    rows = _rows(ck).at[idx.reshape(-1)].set(
        mean.reshape(-1, d).astype(ck.dtype), mode="drop")
    return rows.reshape(ck.shape)


def _probs(s: jax.Array, ok: jax.Array) -> jax.Array:
    """Softmax of s [..., K] (float32) over the entries `ok`; zeros
    where none is."""
    top = jnp.max(jnp.where(ok, s, NEG), axis=-1, keepdims=True)
    e = jnp.where(ok, jnp.exp(s - jnp.where(top == NEG, 0.0, top)), 0.0)
    return e / jnp.maximum(e.sum(axis=-1, keepdims=True), 1e-30)


def select(q: jax.Array, ck: jax.Array, t: jax.Array, sz: Sizes,
           blocks: int) -> jax.Array:
    """q [R, G, g, d] (R queries of ONE sequence), ck [G, blocks * per,
    d] the sequence's compressed keys, t [R] the queries' positions ->
    sel [R, G, topk] int32 block ids, best first (a forced block scores
    +inf), -1 where no block is left."""
    f32 = jnp.float32
    d = q.shape[-1]
    j = jnp.arange(ck.shape[1])
    seen = j[None, :] * sz.stride + sz.kernel <= t[:, None] + 1   # [R, J]
    s = jnp.einsum("rghd,gjd->rghj", q, ck,
                   preferred_element_type=f32) / jnp.sqrt(f32(d))
    p = _probs(s, seen[:, None, None, :])
    p = jnp.where(seen[:, None, :], p.sum(axis=2), NEG)           # [R, G, J]
    width = sz.per + sz.reach
    score = jax.lax.reduce_window(
        jnp.pad(p, ((0, 0), (0, 0), (sz.reach, 0)), constant_values=NEG),
        NEG, jax.lax.max, (1, 1, width), (1, 1, sz.per), "VALID")
    m = jnp.arange(blocks)[None, :]
    own = (t // sz.block)[:, None]
    forced = (m < sz.init_blocks) | ((m > own - sz.local_blocks)
                                     & (m <= own))
    score = jnp.where(forced, jnp.inf, score.transpose(1, 0, 2))
    score = jnp.where(m <= own, score, NEG).transpose(1, 0, 2)    # [R, G, M]
    short = max(sz.topk - blocks, 0)       # a range of fewer blocks
    best, sel = jax.lax.top_k(jnp.pad(
        score, ((0, 0), (0, 0), (0, short)), constant_values=NEG), sz.topk)
    return jnp.where(best == NEG, -1, sel).astype(jnp.int32)


def dense_blocks(t: jax.Array, count: int, sz: Sizes) -> jax.Array:
    """[..., count] int32: blocks 0 .. count - 1 where they hold a
    position <= t, else -1 (what a dense query attends, as a list)."""
    m = jnp.arange(count)
    return jnp.where(m <= (t // sz.block)[..., None], m, -1).astype(jnp.int32)


def attended(sel: jax.Array, t: jax.Array, sz: Sizes
             ) -> tuple[jax.Array, jax.Array]:
    """-> (blocks attended, blocks in the context), each [...] int32,
    for queries at t [...] with the selection sel [..., G, topk]: a
    dense query attends every block, the first kv head's count stands
    for the group (all pick as many)."""
    there = t // sz.block + 1
    picked = (sel[..., 0, :] >= 0).sum(axis=-1)
    return jnp.where(t + 1 > sz.dense_len, picked, there), there


def attend_gathered(q: jax.Array, kpool: jax.Array, vpool: jax.Array,
                    base: jax.Array, sel: jax.Array, t: jax.Array,
                    sz: Sizes) -> jax.Array:
    """One query a sequence: q [B, G, g, d], pools [G, P, d], base [B]
    (positions), sel [B, G, K] block ids (-1: none), t [B] -> [B, G, g,
    d] float32."""
    f32 = jnp.float32
    g_heads, p, d = kpool.shape
    at = jnp.where(sel >= 0, base[:, None, None] // sz.block + sel, 0)
    at = at + jnp.arange(g_heads)[None, :, None] * (p // sz.block)
    keys = kpool.reshape(-1, sz.block, d)[at]          # [B, G, K, block, d]
    values = vpool.reshape(-1, sz.block, d)[at]
    pos = sel[..., None] * sz.block + jnp.arange(sz.block)
    ok = (sel[..., None] >= 0) & (pos <= t[:, None, None, None])
    b, _, k, _ = pos.shape
    s = jnp.einsum("bghd,bgkpd->bghkp", q, keys,
                   preferred_element_type=f32) / jnp.sqrt(f32(d))
    s = s.reshape(*s.shape[:3], k * sz.block)
    p = _probs(s, ok.reshape(b, g_heads, 1, -1)).astype(q.dtype)
    return jnp.einsum("bghk,bgkd->bghd", p,
                      values.reshape(b, g_heads, k * sz.block, d),
                      preferred_element_type=f32)


def _interpret() -> bool:
    """Whether `attend_range` runs Pallas's interpreter: wherever the
    backend is not a TPU (a CPU test runs the same kernel body)."""
    return jax.default_backend() != "tpu"


def attend_range(q: jax.Array, kpool: jax.Array, vpool: jax.Array,
                 base: jax.Array, t: jax.Array, sz: Sizes, tile: int,
                 tiles: int):
    """One query a sequence over its WHOLE range, the range read where
    it lies: q [B, G, g, d], pools [G, P, d], base [B] (positions, a
    multiple of `sz.block`), t [B]; `tile` positions a key tile (whole
    blocks), of which a range holds `tiles` at most -> ([B, G, g, d]
    float32, [B] int32 the keys THE MASK THAT WAS APPLIED let each query
    attend, [B] int32 the positions its walk fetched). What
    `attend_gathered` gives for `dense_blocks(t, ...)` up to the order
    of a float32 sum: row b walks key tiles 0 .. t[b] // tile of ITS OWN
    range (one at least, `tiles` at most) with an online softmax, no
    copy of the range is made and no tile past the row's last is read.
    P reaches `tiles` whole tiles past every base. On a TPU d is whole
    lanes (a multiple of 128: the chip's compiler slices HBM by whole
    tiles)."""
    interpret = _interpret()
    if not interpret and q.shape[-1] % 128:
        raise NotImplementedError(
            f"attend_range on a TPU: head_dim={q.shape[-1]} is not a "
            f"multiple of 128 lanes")
    walked = jnp.clip(t // tile + 1, 1, tiles).astype(jnp.int32)
    o, keys = _attend_range(
        q, kpool, vpool, base.astype(jnp.int32), t.astype(jnp.int32), walked,
        tile=tile, align=sz.block, interpret=interpret)
    return o, keys, walked * tile


# one Pallas kernel under a `jax.jit` of its own, as
# selective_scan._step_slots is and for its reason: a program traces and
# lowers the body once however many layers call it. Grid step b is row
# b; the pools stay in HBM and the row's tiles come by hand-made DMAs
# into two buffers, the next tile (or the NEXT ROW's first) on its way
# while this one is attended, so the walk is one pipeline over every
# (row, tile) pair and makes no step that fetches nothing
@functools.partial(jax.jit, static_argnames=("tile", "align", "interpret"))
def _attend_range(q, kpool, vpool, base, t, walked, *, tile, align,
                  interpret):
    # here, not at the top: a second of import that only a process which
    # serves this op's decode steps should pay
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    rows, g_heads, group, d = q.shape
    scale = 1.0 / math.sqrt(d)

    def walk(base_ref, t_ref, walked_ref, q_ref, k_hbm, v_hbm, o_ref, n_ref,
             kbuf, vbuf, arrived, turn, top, norm, acc):
        """One row: q, o [G, g, d]; the pools whole, in HBM; kbuf, vbuf
        [2, G, tile, d] the two buffers, `turn` which of them the row's
        first tile is in; top, norm [G, g, 1] and acc [G, g, d] the
        online softmax's running maximum, sum and product."""
        row = pl.program_id(0)
        count = walked_ref[row]

        def copies(r, i, slot):
            at = pl.ds(pl.multiple_of(base_ref[r] + i * tile, align), tile)
            return (pltpu.make_async_copy(k_hbm.at[:, at], kbuf.at[slot],
                                          arrived.at[0, slot]),
                    pltpu.make_async_copy(v_hbm.at[:, at], vbuf.at[slot],
                                          arrived.at[1, slot]))

        def fetch(r, i, slot):
            for copy in copies(r, i, slot):
                copy.start()

        @pl.when(row == 0)
        def _():
            turn[0] = 0
            fetch(0, 0, 0)

        first = turn[0]
        top[...] = jnp.full(top.shape, NEG, f32)
        norm[...] = jnp.zeros(norm.shape, f32)
        acc[...] = jnp.zeros(acc.shape, f32)

        def one_tile(i, seen):
            slot = (first + i) % 2

            @pl.when(i + 1 < count)
            def _():
                fetch(row, i + 1, 1 - slot)

            @pl.when((i + 1 == count) & (row + 1 < rows))
            def _():
                fetch(row + 1, 0, 1 - slot)

            for copy in copies(row, i, slot):
                copy.wait()
            pos = i * tile + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
            ok = pos <= t_ref[row]
            for h in range(g_heads):
                s = jax.lax.dot_general(
                    q_ref[h], kbuf[slot, h], (((1,), (1,)), ((), ())),
                    preferred_element_type=f32) * scale        # [g, tile]
                s = jnp.where(ok, s, NEG)
                new_top = jnp.maximum(top[h], s.max(axis=-1, keepdims=True))
                safe = jnp.where(new_top == NEG, 0.0, new_top)
                e = jnp.where(ok, jnp.exp(s - safe), 0.0)
                shrink = jnp.exp(top[h] - safe)
                norm[h] = norm[h] * shrink + e.sum(axis=-1, keepdims=True)
                acc[h] = acc[h] * shrink + jnp.dot(
                    e.astype(q_ref.dtype), vbuf[slot, h],
                    preferred_element_type=f32)
                top[h] = new_top
            return seen + jnp.sum(ok.astype(f32), axis=-1, keepdims=True)

        seen = jax.lax.fori_loop(0, count, one_tile, jnp.zeros((1, 1), f32))
        turn[0] = (first + count) % 2
        o_ref[...] = acc[...] / jnp.maximum(norm[...], 1e-30)
        n_ref[...] = jnp.broadcast_to(seen, n_ref.shape).astype(jnp.int32)

    heads = (None, g_heads, group, d)
    o, keys = pl.pallas_call(
        walk,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(rows,),
            in_specs=[pl.BlockSpec(heads, lambda r, *_: (r, 0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec(heads, lambda r, *_: (r, 0, 0, 0)),
                       pl.BlockSpec((None, 1, 128), lambda r, *_: (r, 0, 0))],
            scratch_shapes=[
                pltpu.VMEM((2, g_heads, tile, d), kpool.dtype),
                pltpu.VMEM((2, g_heads, tile, d), vpool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((g_heads, group, 1), f32),
                pltpu.VMEM((g_heads, group, 1), f32),
                pltpu.VMEM((g_heads, group, d), f32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, f32),
                   jax.ShapeDtypeStruct((rows, 1, 128), jnp.int32)],
        # rows in turn: the buffers and `turn` pass from one to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="attend_range",
    )(base, t, walked, q, kpool, vpool)
    return o, keys[:, 0, 0]


def attend_tiles(q: jax.Array, t: jax.Array, allowed: jax.Array,
                 kpool: jax.Array, vpool: jax.Array, base: jax.Array,
                 sz: Sizes, tile: int, tiles: int | None = None,
                 counted: bool = False):
    """Many queries of ONE sequence: q [R, G, g, d], t [R], `allowed`
    [R, G, M] bool per (query, kv head, block), M a multiple of `tile /
    block`; pools [G, P, d], base a scalar (positions) -> [R, G, g, d]
    float32. Key tiles 0 .. max(t) // tile are walked (`tiles`: a static
    count instead, for a pass that is differentiated); P reaches a whole
    tile past every sequence's last position. `counted`: -> (that, [R]
    int32 the keys the mask that was applied let each query's first kv
    head attend)."""
    f32 = jnp.float32
    r, g_heads, group, d = q.shape
    per_tile = tile // sz.block
    scale = 1.0 / jnp.sqrt(f32(d))

    def walk(i, carry):
        top, norm, acc = carry[:3]
        keys = jax.lax.dynamic_slice_in_dim(kpool, base + i * tile, tile, 1)
        values = jax.lax.dynamic_slice_in_dim(vpool, base + i * tile, tile, 1)
        pos = i * tile + jnp.arange(tile)
        ok = jnp.repeat(jax.lax.dynamic_slice_in_dim(
            allowed, i * per_tile, per_tile, 2), sz.block, axis=2)
        ok = ok & (pos[None, None, :] <= t[:, None, None])    # [R, G, tile]
        ok = ok.transpose(1, 0, 2)[:, None]                   # [G, 1, R, tile]
        s = jnp.einsum("rghd,gpd->ghrp", q, keys,
                       preferred_element_type=f32) * scale
        s = jnp.where(ok, s, NEG)
        new_top = jnp.maximum(top, s.max(axis=-1))
        safe = jnp.where(new_top == NEG, 0.0, new_top)
        e = jnp.where(ok, jnp.exp(s - safe[..., None]), 0.0)
        shrink = jnp.exp(jnp.where(top == NEG, NEG, top - safe))
        norm = norm * shrink + e.sum(axis=-1)
        acc = acc * shrink[..., None] + jnp.einsum(
            "ghrp,gpd->ghrd", e.astype(q.dtype), values,
            preferred_element_type=f32)
        if counted:
            return new_top, norm, acc, carry[3] + ok[0, 0].sum(
                axis=-1, dtype=jnp.int32)
        return new_top, norm, acc

    start = (jnp.full((g_heads, group, r), NEG, f32),
             jnp.zeros((g_heads, group, r), f32),
             jnp.zeros((g_heads, group, r, d), f32))
    if counted:
        start += (jnp.zeros(r, jnp.int32),)
    count = jnp.max(t) // tile + 1 if tiles is None else tiles
    _, norm, acc, *seen = jax.lax.fori_loop(0, count, walk, start)
    out = (acc / jnp.maximum(norm, 1e-30)[..., None]).transpose(2, 0, 1, 3)
    return (out, seen[0]) if counted else out
