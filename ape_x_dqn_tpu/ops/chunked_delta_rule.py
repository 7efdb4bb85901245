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
T and, from ONE product with T, W = T (beta K e^G) beside U_0 = T (beta
V). It also stacks there, where no state is needed, what the chunk's
walk multiplies by: W over Q e^G ([2C, dk]: what the chunk READS of S_0)
and B over (K e^{G_C - G})^T ([C + dk, C]: what it WRITES with U).
`kda.scan.carry` is what is left and sequential, TWO products and four
sums a chunk: U = U_0 - W S_0, O and S_C. A `lax.scan` over chunks
carries S (float32) and the count of chunks walked (`with_chunks`: what
the family's `kda_chunks` reads).

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
factored form has a matmul. Re-basing inside sub-chunks (both factors
<= 1 across sub-chunks, the difference form on the diagonal ones) was
prototyped for ISSUE 47 (XLA's estimated cycles for one layer's forward
+ backward at [1, 3072, 32, 128], compiled for a described v5e; nothing
of it run on a chip): at chunks of 32 it gives back in extra small ops
what the smaller tile saves (64.8 M cycles in sub-chunks of 8 against
64.3 M plain); it pays at chunks of 64 (54.0 M against plain 64's
74.0 M), which waits for the configuration's `kda_chunk` to follow the
program (PERF.md section 7).

THE SOLVE IS BLOCK FORWARD SUBSTITUTION, NOT A POWER SERIES. (I + A)^-1
= sum_n (-A)^n is finite (A is nilpotent) and is six matmuls by
doubling, but at random weights every key of a sequence is nearly one
vector, A is close to beta x the all-ones triangle, and the series'
terms reach 0.5^32 C(62, 31) = 1e8 with alternating signs where the
inverse's entries are below 1: float32 keeps nothing of it. Diagonal
blocks of `_BASE` = 8 are inverted by the doubled series (terms up to
C(6, 3) = 20: harmless), then pairs of blocks are joined,
[[P, 0], [R, Q]]^-1 = [[P^-1, 0], [-Q^-1 R P^-1, Q^-1]], twice to 32
(three times to 64): exact algebra, as stable as substitution. Every
step multiplies WHOLE [C, C] matrices that are block diagonal (a join is
inv - inv [[0, 0], [R, 0]] inv; products of block diagonal matrices are
block diagonal: the same sums, and zeros) under masks, eight products a
chunk at 32: gathered 8 x 8 and 16 x 16 blocks took 22, over arrays
whose rows fill a sixteenth of a vector register (ISSUE 47).

WHY NOT SEVERAL CHUNKS A SCAN ITERATION (ISSUE 47 asked for four, with
everything under `kda.scan.intra` made for the group at once; built,
held to the recurrence and measured on the chip: `kimi_linear_offline`,
one v5e, my chip runs, PR 47; samples/s | `peak_hbm_gib` with the
check). On ISSUE 46's arithmetic 1 chunk an iteration read 3.040 |
12.878, 2 3.101, 4 3.172 | 13.291, 8 3.040: the gain at four was real
and its cost was CODE - XLA:TPU unrolls a batched product over its batch,
so the scan's code grew from 21 to 67 MiB a layer, `train_many`'s by
0.146 GiB, the check's programs likewise, and the cell's peak by 3.2%
against a bound of 1%. On THIS file's arithmetic 1 reads 3.178 |
12.888 and 2 (its solves as one block diagonal [2C, 2C] matrix) 3.108 |
13.002: what the group bought was larger arrays for the small ops, the
whole-matrix products buy the same at one chunk an iteration with no
more code than before, and beside them a group only adds its code.
The scan's 1 / C term (`CHUNK`'s comment) was never launches: an
iteration's ops are each in proportion to their data (PERF.md section 6).

PRECISION. The cumulative g, the solve, S and every product in the scan
are float32, the matmuls at `Precision.HIGHEST`: the products are 7 dk
dv FLOP a token and head, a hundredth of a decoder step's matmuls even
at six bfloat16 passes each, and what bounds the scan is the VPU tile
above and the chunk-to-chunk latency, not the MXU. The net rounds q, k
and v where IT holds them in its compute dtype (models/kimi_linear_q.py,
through `reduce_precision`) and passes float32; nothing is rounded here.

THE BACKWARD PASS IS A RULE OF ITS OWN (ISSUE 54; written for ISSUE 53,
whose PR was refused for a file it added to the benchmark): `_chunk` is a
`jax.custom_vjp` inside the scan, whose transpose still carries dS from
chunk to chunk; the int32 count stays outside it. Autodiff under a
`jax.checkpoint` made the chunk again and transposed every op of it: 33
products a chunk at 32 (the solve's eight, again, and sixteen transposes
of them) and about fifteen passes over the [C, C, dk] tile. The algebra
needs eight products and the tile made once, under `kda.scan.back`:

- the carry (`.back.carry`): W | U_0 = T (beta [K e^G | V]) and U = U_0
  - W S_0 are made again (two products), then the four transposes of the
  walk's two: d writes = [dO; dS_C] U^T, dU = writes^T [dO; dS_C],
  d reads = [-dU; dO] S_0^T and dS_0 = e^{G_C} dS_C + reads^T [-dU; dO];
- the solve (`.back.solve`, `_solve_back`): wu = T rhs has d rhs = T^T
  d wu and dA = -(d rhs) wu^T, strictly lower: TWO products, and
  `_unit_lower_inverse` is not run again;
- the tile (`.back.tile`, `_tile_back`; with `_tile` the pair a re-based
  tile replaces): A is the tile of the rows beta k against the keys, B
  of the rows q. With E_ijc = exp(G_ic - G_jc) (made again, masked
  BEFORE the exponential as the forward's: no infinity meets a zero
  here either) and keys_ijc = E_ijc k_jc, THREE reductions:
      d rows_ic = sum_j dA_ij keys_ijc,     dq_ic = sum_j dB_ij keys_ijc,
      dk_jc = sum_i (dA_ij rows_ic + dB_ij q_ic) E_ijc
  (dk_i += beta_i d rows_i, d beta_i += k_i . d rows_i: no division by
  beta, and A's kk is not needed again). THE DECAYS' GRADIENT NEEDS NO
  PASS OF ITS OWN: every entry of the tile is exp(G_ic - G_jc) times a
  product in the same channel c, so dG_ic = rows_ic d rows_ic + q_ic
  dq_ic - k_ic dk_ic, exactly (gated linear attention's q dq - k dk);
- the [C, dk]-sized rest (`_factors`: e^G on W's and O's keys, e^{G_C -
  G} on the state's, e^{G_C} on S_0, the running sum of g) is a
  `jax.vjp` inside the rule: it is not where the time is.

SAVED a chunk by the forward rule: the chunk's inputs (the scan's own
xs: not stacked again), S_0 ([T / C, dk, dv] a head: 201 MB a layer at
3,072 trained positions in chunks of 32, as before) and T | B as ONE
[C, 2C] array (25 MB a layer; 50 MB as XLA:TPU lays it out, a [32, 64]
float32 tile filling half of its (8, 128) tiles - T and B apart cost
150 MB, measured: PERF.md section 6, PR 53), all alive only inside the
block's own recomputation. MADE AGAIN: W | U_0 (100 MB a layer if
kept), the tile's decays and keys (1.6 GB a layer); nothing of size
[T, dk, dv] or [T, C, dk] exists. What holds the rule to the truth is
autodiff of the RECURRENCE (tests/test_chunked_delta_rule.py) and the
cell's gradient check against benchmarks/reference/: neither knows it.

THE WALK IS AN INLINED `jit` (`_walk`, the chunk static): a
`custom_vjp` traces its function anew at every call of the op - sixteen
a learner step, a net's four KDA layers in four applications - where
the per-chunk `jax.checkpoint` before it was traced once; a trace is
set-up time (cached `setup_s` +9% on a bound of 10%, all of it tracing:
PERF.md section 6, PR 53). `inline=True` leaves no call in the program and one
trace for all calls of the same shapes.

A T OFF A CHUNK is padded behind with positions that leave the state as
it was (g = 0, beta = 0, and zeros): the server's `apply_window` sends
windows of any length.
"""

from __future__ import annotations

import functools

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
BACK = "kda.scan.back"          # the backward rule's ops, inside SCOPE too
BACK_TILE, BACK_SOLVE, BACK_CARRY = (
    BACK + ".tile", BACK + ".solve", BACK + ".carry")


def _mm(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.matmul(a, b, precision=_HI)


def _t(x: jax.Array) -> jax.Array:
    return jnp.swapaxes(x, -1, -2)


def _unit_lower_inverse(a: jax.Array) -> jax.Array:
    """a [..., C, C] strictly lower triangular, C = `_BASE` x a power of
    two (or below `_BASE`) -> (I + a)^-1 (module docstring). Every
    product is of whole [C, C] matrices that are block diagonal."""
    c = a.shape[-1]
    s = min(c, _BASE)
    at = jnp.arange(c)
    row, col = at[:, None], at[None, :]
    x = jnp.where(row // s == col // s, -a, 0.0)
    inv = jnp.eye(c, dtype=a.dtype) + x
    power, reach = x, 2
    while reach < s:                    # (I + x)(I + x^2)(I + x^4) ...
        power = _mm(power, power)
        inv = inv + _mm(inv, power)
        reach *= 2
    while s < c:        # [[P, 0], [R, Q]]^-1 = inv - inv [[0, 0], [R, 0]] inv
        below = jnp.where((row // (2 * s) == col // (2 * s))
                          & (row // s > col // s), a, 0.0)
        inv = inv - _mm(_mm(inv, below), inv)
        s *= 2
    return inv


def _below(c: int) -> jax.Array:
    """[C, C]: j < i, where A and dA live."""
    at = jnp.arange(c)
    return at[:, None] > at[None, :]


def _decays(total: jax.Array) -> jax.Array:
    """G [.., C, dk] -> exp(G_i - G_j) for j <= i and 0 above, [.., C, C,
    dk]: the other triangle is masked BEFORE the exponential."""
    at = jnp.arange(total.shape[-2])
    upto = (at[:, None] >= at[None, :])[..., None]           # j <= i
    apart = jnp.where(
        upto, total[..., :, None, :] - total[..., None, :, :], 0.0)
    return jnp.where(upto, jnp.exp(apart), 0.0)


def _tile(q, k, total):
    """THE TILE: -> kk, qk [.., C, C], sum_c x_ic k_jc exp(G_ic - G_jc)
    for j <= i (0 above) with x = k and x = q. `_tile_back` is its
    backward rule: a re-based tile replaces the pair."""
    keys = _decays(total) * k[..., None, :, :]
    kk = (k[..., :, None, :] * keys).sum(axis=-1)
    qk = (q[..., :, None, :] * keys).sum(axis=-1)
    return kk, qk


def _tile_back(rows, q, k, total, d_a, d_qk):
    """The cotangents of a = tile(rows, k) and qk = tile(q, k) (the
    solve's A is the tile of beta k: `_chunk_bwd`) -> d rows, dq, the
    COLUMN part of dk [.., C, dk] and dG: three reductions over the
    recomputed tile and, for dG, the identity of the module docstring."""
    decay = _decays(total)
    keys = decay * k[..., None, :, :]
    d_rows = (d_a[..., None] * keys).sum(axis=-2)
    dq = (d_qk[..., None] * keys).sum(axis=-2)
    dk = ((d_a[..., None] * rows[..., :, None, :]
           + d_qk[..., None] * q[..., :, None, :]) * decay).sum(axis=-3)
    return d_rows, dq, dk, rows * d_rows + q * dq - k * dk


def _solve_back(inv, wu, d_wu):
    """THE SOLVE: wu = T rhs, T = (I + A)^-1 -> d rhs = T^T d wu and
    dA = -(d rhs) wu^T, its strictly lower part: two products."""
    d_rhs = _mm(_t(inv), d_wu)
    d_a = jnp.where(_below(inv.shape[-1]), -_mm(d_rhs, _t(wu)), 0.0)
    return d_rhs, d_a


def _factors(q, k, g):
    """The [C, dk]-sized factors of a chunk: G, K e^G, Q e^G,
    K e^{G_C - G} and e^{G_C} [.., dk, 1]; every exponent <= 0."""
    total = jnp.cumsum(g, axis=-2)                           # G [.., C, dk]
    grown = jnp.exp(total)                                   # e^G
    return (total, k * grown, q * grown,
            k * jnp.exp(total[..., -1:, :] - total),
            grown[..., -1, :, None])


def _operands(factors, v, beta, inv, qk):
    """What the chunk's walk multiplies by, stacked where no state is
    needed: W | U_0 [.., C, dk + dv], what the chunk reads of S_0 (W over
    Q e^G [.., 2C, dk]) and what it writes with U (B over (K e^{G_C -
    G})^T [.., C + dk, C])."""
    _, k_grown, q_grown, k_out, _ = factors
    dk = k_grown.shape[-1]
    wu = _mm(inv, beta[..., None] * jnp.concatenate([k_grown, v], axis=-1))
    reads = jnp.concatenate([wu[..., :dk], q_grown], axis=-2)
    writes = jnp.concatenate([qk, _t(k_out)], axis=-2)
    return wu, reads, writes


def _chunk_fwd(s0, q, k, v, g, beta):
    c, dk = q.shape[-2:]
    with jax.named_scope(INTRA):
        factors = _factors(q, k, g)
        kk, qk = _tile(q, k, factors[0])
        inv = _unit_lower_inverse(
            beta[..., None] * jnp.where(_below(c), kk, 0.0))
        wu, reads, writes = _operands(factors, v, beta, inv, qk)
    with jax.named_scope(CARRY):
        read = _mm(reads, s0)               # W S_0 over (Q e^G) S_0
        u = wu[..., dk:] - read[..., :c, :]
        written = _mm(writes, u)            # B U over (K e^{G_C - G})^T U
        o = read[..., c:, :] + written[..., :c, :]
        s1 = factors[-1] * s0 + written[..., c:, :]
    # T | B saved as ONE [C, 2C] array: a [32, 32] float32 matrix fills a
    # quarter of its (8, 128) tiles in HBM, and two of them cost two
    return (s1, o), (s0, q, k, v, g, beta,
                     jnp.concatenate([inv, qk], axis=-1))


@jax.custom_vjp
def _chunk(s0, q, k, v, g, beta):
    """One chunk: S [B, H, dk, dv] at its start, the chunk's q, k, g
    [B, H, C, dk], v [B, H, C, dv], beta [B, H, C] -> (S after it, o
    [B, H, C, dv])."""
    return _chunk_fwd(s0, q, k, v, g, beta)[0]


def _chunk_bwd(saved, cotangents):
    """The module docstring's BACKWARD PASS: T and B come saved; the
    factors, W | U_0, U and the tile are made again."""
    s0, q, k, v, g, beta, solved = saved
    d_s1, d_o = cotangents
    c, dk = q.shape[-2:]
    # a `custom_vjp`'s backward function opens no scope of its own: every
    # op of it, the last sums too, is under `kda.scan` here
    with jax.named_scope(SCOPE), jax.named_scope(BACK):
        with jax.named_scope(BACK_CARRY):
            inv, qk = solved[..., :c], solved[..., c:]
            factors, factors_back = jax.vjp(_factors, q, k, g)
            wu, reads, writes = _operands(factors, v, beta, inv, qk)
            u = wu[..., dk:] - _mm(wu[..., :dk], s0)
            d_written = jnp.concatenate([d_o, d_s1], axis=-2)
            d_writes = _mm(d_written, _t(u))                 # [.., C + dk, C]
            d_u = _mm(_t(writes), d_written)
            d_read = jnp.concatenate([-d_u, d_o], axis=-2)
            d_reads = _mm(d_read, _t(s0))                    # [.., 2C, dk]
            d_s0 = factors[-1] * d_s1 + _mm(_t(reads), d_read)
        with jax.named_scope(BACK_SOLVE):
            d_rhs, d_a = _solve_back(inv, wu, jnp.concatenate(
                [d_reads[..., :c, :], d_u], axis=-1))
            d_base = beta[..., None] * d_rhs    # of beta [K e^G | V]'s rows
        with jax.named_scope(BACK_TILE):
            d_rows, dq, dk_col, d_total = _tile_back(
                beta[..., None] * k, q, k, factors[0], d_a,
                d_writes[..., :c, :])
        # the [C, dk]-sized rest is autodiff's: the factors' cotangents
        dq_f, dk_f, dg = factors_back((
            d_total, d_base[..., :dk], d_reads[..., c:, :],
            _t(d_writes[..., c:, :]),
            (d_s1 * s0).sum(axis=-1, keepdims=True)))
        d_beta = ((d_rhs * jnp.concatenate([factors[1], v], axis=-1)
                   ).sum(axis=-1) + (k * d_rows).sum(axis=-1))
        return (d_s0, dq + dq_f, beta[..., None] * d_rows + dk_col + dk_f,
                d_base[..., dk:], dg, d_beta)


_chunk.defvjp(_chunk_fwd, _chunk_bwd)


def chunked_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array,
                       g: jax.Array, beta: jax.Array,
                       state: jax.Array | None = None, *,
                       chunk: int = CHUNK, with_chunks: bool = False):
    """See the module docstring. `chunk`: positions a chunk (a power of
    two); `with_chunks`: also the chunks walked, int32."""
    if chunk & (chunk - 1):
        raise ValueError(f"chunk={chunk}: a power of two")
    if state is None:
        state = jnp.zeros(
            (q.shape[0], q.shape[2], q.shape[3], v.shape[-1]), jnp.float32)
    o, state, walked = _walk(q, k, v, g, beta, state, chunk)
    return (o, state, walked) if with_chunks else (o, state)


@functools.partial(jax.jit, static_argnums=6, inline=True)
def _walk(q, k, v, g, beta, state, chunk):
    """The scan over chunks, inlined where it is called and TRACED ONCE
    for all calls of the same shapes (the module docstring's THE WALK)."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % chunk
    n = (t + pad) // chunk

    def chunks(x):      # [B, T, H, ...] -> [N, B, H, C, ...]
        x = x.astype(jnp.float32)
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(b, n, chunk, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    with jax.named_scope(SCOPE):
        def step(carry, xs):    # the int32 count stays outside the rule
            s1, o = _chunk(carry[0], *xs)
            return (s1, carry[1] + 1), o

        (state, walked), o = jax.lax.scan(
            step, (state.astype(jnp.float32), jnp.int32(0)),
            tuple(chunks(x) for x in (q, k, v, g, beta)))
        # [N, B, H, C, dv] -> [B, T, H, dv]
        o = jnp.moveaxis(o, 0, 1).transpose(0, 1, 3, 2, 4).reshape(
            b, n * chunk, h, dv)[:, :t]
    return o, state, walked
