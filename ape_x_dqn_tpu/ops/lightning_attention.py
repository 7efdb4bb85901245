"""Linear attention with ONE SCALAR DECAY A HEAD (Lightning
Attention-2, arXiv:2401.04658, as MiniCPM-SALA's `lightning-attn`
layers use it), the second recurrent op beside
ops/chunked_delta_rule.py and no generalisation of it: there is no
solve and no per-channel decay, only

    S_t = lambda_h S_{t-1} + k_t^T v_t          S float32 [d_k, d_v]
    o_t = q_t S_t * scale

per head h, `lambda_h = exp(-slope_h)`. Three forms of the same function:

- `step`: one position, the recurrence as written: the plain form the
  other two are tested against.
- `step_slots`: one position of B rows whose matrices live in a pool
  `[slots + 1, H, d, d]` between calls (the inference server's decode
  step: models/minicpm_sala_q.py `extend` at n = 1), as ONE Pallas
  kernel that addresses the pool in place, where a gather, `step`, a
  `where` and a scatter made four passes over `[B, H, d, d]`. It owns
  the slot addressing (`slot` is scalar-prefetched, the pool's block
  index is `(slot[b], a block of HEADS_A_BLOCK heads)`, and the pool is
  an `input_output_aliases` pair: the block that was read is the block
  that is written and no other byte of the pool moves), `fresh` (the row
  starts from zeros whatever its slot holds) and `valid` (a row that
  does not count keeps its matrix - zeros if it was fresh - and its
  output is garbage nobody reads). A SLOT NAMED TWICE: the server pads
  a batch with rows that all name the scratch slot, so `slot` may
  repeat. The kernel's pipeline fetches a row's block while the row
  before it is computed, and between grid steps that name the same
  block it neither fetches nor writes back; so of two rows on one slot
  the second may read what the first found, not what it left. That is a
  read-after-write hazard on the slot named twice and on no other, and
  the caller's contract is that nobody reads such a slot (real slots
  never repeat within a batch: parallel/inference_server.py
  `_collect`). Rows run fastest in the grid, so a run of padding rows
  moves the scratch slot's blocks once, not once a row (the constant's
  comment has the chip's readings). On a backend that is not a TPU the
  same kernel body runs in Pallas's interpreter.
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

Everything here is float32 at `Precision.HIGHEST` (the kernel's
products are the VPU's, float32 as written): q, k and v arrive already
rounded to the compute dtype where the net holds them, the state, the
decays and the products with either are exact to float32.
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

# heads a block of `step_slots` (1 MiB at heads of 128; in and out, each
# double-buffered, 4 MiB of VMEM). Six layers' pools [49, 32, 128, 128]
# on the chip, ms for the six calls at 8 | 16 | 32 heads a block: 16
# rows 0.713 | 0.667 | 0.676, 32 rows 1.391 | 1.289 | 1.291 (74-76% of
# the bytes' time at 819 GB/s, where the gather, `step`, `where` and
# scatter took 2.22 and 4.94), 17 rows + 15 on the scratch slot 1.222 |
# 1.161 | 1.158. With the heads unrolled in Python the kernel at 16 heads
# a block read 0.653, 1.272 and 0.954 (1.274 with the heads, not the
# rows, fastest in the grid); but every decode bucket lowers six kernels,
# and sixteen heads' worth of body cost 0.45 s a bucket, 4.6 s of the
# cell's set-up (PERF.md section 6, PR 56)
HEADS_A_BLOCK = 16

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


def _interpret() -> bool:
    """Whether `step_slots` runs Pallas's interpreter: wherever the
    backend is not a TPU (a CPU test runs the same kernel body)."""
    return jax.default_backend() != "tpu"


def step_slots(pool: jax.Array, slot: jax.Array, fresh: jax.Array,
               valid: jax.Array, q: jax.Array, k: jax.Array, v: jax.Array,
               slope: jax.Array, scale: float
               ) -> tuple[jax.Array, jax.Array]:
    """pool [slots + 1, H, d, d] float32, slot [B] int32, fresh, valid
    [B] bool, q, k, v [B, H, d] of ONE position -> (o [B, H, d] float32,
    the pool with row b's matrix at `slot[b]` after it). What
    `pool.at[slot].set(where(valid, after, before))` computes with
    `before = where(fresh, 0, pool[slot])` and `after = step(q, k, v,
    before, ...)[1]`, in one pass over the rows' matrices and in place
    when the pool is donated; a slot named twice is the module
    docstring's. `slot` has to lie inside the pool: the kernel's block
    fetch does not clamp an index as XLA's gather did (the server's
    ledger refuses any other slot on the host: parallel/slot_pool.py
    `admit`)."""
    # here, not at the top: a second of import that only a process which
    # serves this net's decode steps should pay
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    b, heads, d = q.shape
    hb = HEADS_A_BLOCK if heads % HEADS_A_BLOCK == 0 else heads

    def block(slot_ref, fresh_ref, valid_ref, lam_ref, q_ref, k_ref, v_ref,
              s_ref, o_ref, out_ref):
        """One row's block of heads: lam [hb, 1, d], q, k, v, o [1, hb,
        d], the matrices [hb, d, d] in and out. A head at a time on the
        VPU, exact float32, each matrix touched once; a head's q and k
        become columns by a masked sum along the lanes."""
        del slot_ref                        # the index maps read it
        row = pl.program_id(1)
        is_fresh, counts = fresh_ref[row] != 0, valid_ref[row] != 0
        diagonal = (jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
                    == jax.lax.broadcasted_iota(jnp.int32, (d, d), 1))

        def column(ref, j):                 # [1, hb, d] -> head j's [d, 1]
            return jnp.sum(jnp.where(diagonal, ref[0, pl.ds(j, 1), :], 0.0),
                           axis=1, keepdims=True)

        def head(j, carry):
            before = jnp.where(is_fresh, 0.0, s_ref[j])
            after = (lam_ref[j] * before
                     + column(k_ref, j) * v_ref[0, pl.ds(j, 1), :])
            o_ref[0, pl.ds(j, 1), :] = scale * jnp.sum(
                column(q_ref, j) * after, axis=0, keepdims=True)
            out_ref[j] = jnp.where(counts, after, before)
            return carry

        jax.lax.fori_loop(0, hb, head, None)

    # rows fastest: a run of rows on one slot moves its blocks once
    vectors = pl.BlockSpec((1, hb, d), lambda h, row, *_: (row, h, 0))
    matrices = pl.BlockSpec(
        (None, hb, d, d), lambda h, row, slot_ref, *_: (slot_ref[row], h, 0, 0))
    lam = jnp.broadcast_to(jnp.exp(-slope)[:, None, None], (heads, 1, d))
    o, pool = pl.pallas_call(
        block,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(heads // hb, b),
            in_specs=[pl.BlockSpec((hb, 1, d), lambda h, row, *_: (h, 0, 0)),
                      vectors, vectors, vectors, matrices],
            out_specs=[vectors, matrices]),
        out_shape=[jax.ShapeDtypeStruct((b, heads, d), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 7 (the three prefetched scalars count) is output 1
        input_output_aliases={7: 1},
        interpret=_interpret(), name="lightning_step_slots",
    )(slot.astype(jnp.int32), fresh.astype(jnp.int32),
      valid.astype(jnp.int32), lam, q.astype(f32), k.astype(f32),
      v.astype(f32), pool)
    return o, pool


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
