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

Three entries over one definition (`_advance`, one position of every
row):

- `step`: one position a row, the recurrence as written on states the
  caller holds as rows: the plain form `step_slots` is tested against
  (no program calls it since `step_slots`).
- `step_slots`: one position of R rows whose states live in a pool
  `[slots + 1, d_state, channels]` between calls (the inference server's
  decode step: models/jamba_q.py `extend` at rank-1 `obs`), as ONE
  Pallas kernel that addresses the pool in place, where a gather, a
  `where`, `step` and a scatter made three passes and a select over
  `[R, d_state, channels]`. It owns the slot addressing (`slot` is
  scalar-prefetched, the pool's block index is `(slot[r], a block of
  CHANNELS_A_BLOCK channels)`, and the pool is an
  `input_output_aliases` pair: the block that was read is the block
  that is written and no other byte of the pool moves), `fresh` (the row
  starts from zeros whatever its slot holds) and `valid` (a row that
  does not count keeps its state - zeros if it was fresh - as `step`'s
  does, by a step of 0, and its output is garbage nobody reads). The
  body is `_advance`'s arithmetic on one row: the same float32 products
  in the same order (on the chip y and the states came out `step`'s to
  the bit), b and c turned from the row's `[1, d_state]` into columns
  INSIDE the kernel by a masked sum along the lanes (broadcast outside
  to `[R, d_state, 128]` read the same: 3.524 against 3.527 ms), x,
  delta, b, c and y moved eight rows at a time (a float32 tile's
  sublanes: one fetch where eight rows' worth of 20 KiB ones were). A
  SLOT NAMED TWICE: the server pads a batch with rows that all name the
  scratch slot, so `slot` may repeat. The kernel's pipeline fetches a
  row's block while the row before it is computed, and between grid
  steps that name the same block it neither fetches nor writes back; so
  of two rows on one slot the second may read what the first found, not
  what it left. That is a read-after-write hazard on the slot named
  twice and on no other, and the caller's contract is that nobody reads
  such a slot (real slots never repeat within a batch:
  parallel/inference_server.py `_collect`). Rows run fastest in the
  grid, so a run of padding rows moves the scratch slot's blocks once,
  not once a row (the constant's comment has the chip's readings). On a
  backend that is not a TPU the same kernel body runs in Pallas's
  interpreter.
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
it are float32 as written (the VPU's), in the kernel as outside it.
Scopes are the caller's (`jamba.mamba.scan`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# positions a chunk of `chunked`: the loop's body is this many positions
# unrolled, h carried once a chunk. The one length read on the chip: a
# prefill dispatch of 8 x 2,048 tokens through 26 such layers and the
# rest of Jamba2's stack takes 0.84 s (PERF.md section 6, PR 57)
CHUNK = 16

# channels a block of `step_slots`: a whole row's state at the published
# width (320 KiB at d_state 16; in and out, each double-buffered, 1.25 MiB
# of VMEM), one row a grid step. 26 layers' pools [257, 16, 5120] on the
# chip, ms for the 26 calls at 5120 | 2560 | 1280 channels a block: 126
# rows + 2 on the scratch slot 3.527 | 4.381 | 6.760, 128 rows 3.540 |
# 4.387 | 9.700, 64 rows 1.809 | 2.238 | 3.420 (74-75% of the bytes' time
# at 819 GB/s, where the gather, `where`, `step` and scatter took 12.82,
# 12.82 and 5.74): a grid step costs about what 100 KiB of traffic does,
# so the largest block wins. The body as plain vector code over the
# block; walked 256 | 512 | 1024 lanes at a time in a loop it read 4.077 |
# 3.704 | 3.543. Several rows a grid step, their states moved by
# hand-made DMAs into two sets of buffers, read 3.527 | 3.427 | 3.384 at
# 2 | 4 | 8 rows (3.327 at 8 with the 512-lane loop: 78.8%): 0.2 ms of a
# 23 ms step for four times the code and four times the compile (6.0 s
# for the 26 sites against 1.5) - not taken (PERF.md section 6, PR 58)
CHANNELS_A_BLOCK = 5120
# rows whose x, delta, b, c and y travel together in `step_slots`: a
# float32 tile's sublanes
_ROWS_A_FETCH = 8


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


def _interpret() -> bool:
    """Whether `step_slots` runs Pallas's interpreter: wherever the
    backend is not a TPU (a CPU test runs the same kernel body)."""
    return jax.default_backend() != "tpu"


def step_slots(pool: jax.Array, slot: jax.Array, fresh: jax.Array,
               valid: jax.Array, x: jax.Array, delta: jax.Array,
               a: jax.Array, b: jax.Array, c: jax.Array, d: jax.Array
               ) -> tuple[jax.Array, jax.Array]:
    """ONE position a row, the rows' states in a pool. pool [slots + 1,
    N, D] float32, slot [R] int32, fresh, valid [R] bool, x, delta [R,
    D], a [N, D], b, c [R, N], d [D] -> (y [R, D] float32, the pool with
    row r's state at `slot[r]` after it). What `pool.at[slot].set(after)`
    computes with `before = where(fresh, 0, pool[slot])` and `y, after =
    step(before, x, delta, a, b, c, d, valid)`, in one pass over the
    rows' states and in place when the pool is donated; a slot named
    twice is the module docstring's. `slot` has to lie inside the pool:
    the kernel's block fetch does not clamp an index as XLA's gather did
    (the server's ledger refuses any other slot on the host:
    parallel/slot_pool.py `admit`)."""
    width = x.shape[1]
    cb = CHANNELS_A_BLOCK if width % CHANNELS_A_BLOCK == 0 else width
    return _step_slots(pool, slot, fresh, valid, x, delta, a, b, c, d,
                       cb=cb, interpret=_interpret())


# a function of its own under `jax.jit`: the 26 Mamba layers of a decode
# program then trace the kernel ONCE and lower it ONCE, as one function
# the program calls 26 times (XLA inlines it) - each call site lowered
# by itself cost 0.1 s, 22 s over the server's eight decode buckets
# BEFORE the persistent compile cache is asked, +16% of the cell's
# `setup_s` (PERF.md section 6, PR 58). What decides the kernel's form
# is a static argument, so a trace is never another form's
@functools.partial(jax.jit, static_argnames=("cb", "interpret"))
def _step_slots(pool, slot, fresh, valid, x, delta, a, b, c, d, *, cb,
                interpret):
    # here, not at the top: a second of import that only a process which
    # serves this op's decode steps should pay
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    rows, width = x.shape
    n = pool.shape[1]
    # x, delta, b, c and y move `rb` rows at a time, fetched when the
    # row's index crosses a multiple of it: a block's sublanes have to
    # be whole tiles or the whole array
    rb = min(rows, _ROWS_A_FETCH)
    pad = -rows % rb

    def block(slot_ref, fresh_ref, valid_ref, a_ref, d_ref, x_ref,
              delta_ref, b_ref, c_ref, h_ref, y_ref, out_ref):
        """One row's block of channels: a, the state in and out [N, cb],
        d [1, cb]; x, delta, y [rb, cb] and b, c [rb, N] of the `rb`
        rows this one is among. `_advance` on the VPU, float32 as
        written; b and c become columns by a masked sum along the
        lanes."""
        del slot_ref                        # the index maps read it
        row = pl.program_id(1)
        at = pl.ds(row % rb, 1)
        diagonal = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
                    == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))

        def column(ref):                    # the row's [1, N] -> [N, 1]
            return jnp.sum(jnp.where(diagonal, ref[at, :], 0.0), axis=1,
                           keepdims=True)

        x_row = x_ref[at, :]
        step_size = jnp.where(valid_ref[row] != 0, delta_ref[at, :], 0.0)
        before = jnp.where(fresh_ref[row] != 0, 0.0, h_ref[...])
        after = (jnp.exp(step_size * a_ref[...]) * before
                 + (step_size * x_row) * column(b_ref))
        y_ref[at, :] = (jnp.sum(after * column(c_ref), axis=0, keepdims=True)
                        + d_ref[...] * x_row)
        out_ref[...] = after

    def padded(arr):
        arr = arr.astype(f32)
        return jnp.pad(arr, ((0, pad), (0, 0))) if pad else arr

    # rows fastest: a run of rows on one slot moves its block once
    channels = pl.BlockSpec((rb, cb), lambda j, row, *_: (row // rb, j))
    coordinates = pl.BlockSpec((rb, n), lambda j, row, *_: (row // rb, 0))
    states = pl.BlockSpec(
        (None, n, cb), lambda j, row, slot_ref, *_: (slot_ref[row], 0, j))
    y, pool = pl.pallas_call(
        block,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(width // cb, rows),
            in_specs=[pl.BlockSpec((n, cb), lambda j, row, *_: (0, j)),
                      pl.BlockSpec((1, cb), lambda j, row, *_: (0, j)),
                      channels, channels, coordinates, coordinates, states],
            out_specs=[channels, states]),
        out_shape=[jax.ShapeDtypeStruct((rows + pad, width), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 9 (the three prefetched scalars count) is output 1
        input_output_aliases={9: 1},
        interpret=interpret, name="selective_scan_step_slots",
    )(slot.astype(jnp.int32), fresh.astype(jnp.int32),
      valid.astype(jnp.int32), a.astype(f32), d.astype(f32)[None, :],
      padded(x), padded(delta), padded(b), padded(c), pool)
    return y[:rows], pool


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
