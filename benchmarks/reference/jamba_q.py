"""Plain reference: AI21-Jamba2-3B's hybrid decoder (`model_type` jamba)
as a token-level Q-network, the FULL FORWARD PASS over one whole token
history in float32 `jax.numpy`, written from the catalog row's
config.json keys
(https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json)
and its `described_as` ("Mamba-1 + attention 7:1", dense); what those
leave open is listed under `assumed` in
benchmarks/configs/jamba2_3b_1chip.json. No cache, no chunks, no
batching, no slots, nothing imported from the system under test (the
pieces a decoder reference shares with another - RMSNorm, SwiGLU, the
rounding to fewer bits - come from reference/glm_moe_q.py); every entry
point runs under `jax.default_matmul_precision("highest")`. The
recurrence is a plain loop over positions (a scan that carries h
`[channels, d_state]`, the shapes as published), attention materialises
its scores, `QUERY_BLOCK` queries side by side so that ten thousand
positions fit: that changes what is alive, not what is computed.

One sequence: tokens [T].

- x0 = E[token]. Block, both kinds: x = x + Mixer(N1(x)); x = x +
  MLP(N2(x)); RMSNorm eps; MLP = W_d[silu(W_g y) * W_u y] on every layer.
  Layer i is attention iff `kinds[i]` says so (i % 14 == 7 as published).
  Q = N(x) E^T (the head is the embedding). No position encoding.
- mamba, u [T, H]: [x | z] = u W_in; x_t = silu(sum_j w_j x_{t - (K-1) +
  j} + b_conv) (zeros before position 0); [dt | B | C] = x W_x, each
  through its own RMSNorm with a gain; delta = softplus(dt W_dt + b_dt);
  A = -exp(A_log); h_t = exp(delta_t (x) A) * h_{t-1} + (delta_t x_t) (x)
  B_t (h_{-1} = 0, float32); y_t = h_t C_t + D * x_t; out = (y *
  silu(z)) W_out.
- attention: q [T, heads, d], k, v [T, kv heads, d], no bias, no
  rotation; causal softmax at 1 / sqrt(d); o W_o.

`mantissa_bits`: every value the system holds in its compute dtype is
rounded to that many explicit bits (None / 23: the reference proper);
delta, the softplus, the exponential and h stay float32.

Departures a check must refuse, each a field of `Sizes`:
`carry_rounded` (h rounded to the STATED precision's 7 bits after every
position: a carry kept in bfloat16), `no_dt_bias` (delta without b_dt),
`no_inner_norms` (dt, B and C straight from W_x), `conv_tail_dropped`
(the filter sees no row before its own: the tail dropped at every step's
boundary), `padding_advances_from` p >= 0 (every position from p on is
followed by a padding position that advances h with that position's own
decay and input), `no_skip` (D * x left out), `attn_one_short` (the
query at t attends the positions before t and not its own).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmarks.reference.glm_moe_q import rms_norm, rounder, swiglu

QUERY_BLOCK = 256
MAMBA, ATTENTION = "mamba", "attention"
CARRY_BITS = 7      # what `carry_rounded` keeps of h: bfloat16's


class Sizes(NamedTuple):
    kinds: tuple               # one kind per layer
    heads: int
    kv_heads: int
    head_dim: int
    d_inner: int
    d_state: int
    dt_rank: int
    d_conv: int
    rms_norm_eps: float
    carry_rounded: bool = False
    no_dt_bias: bool = False
    no_inner_norms: bool = False
    conv_tail_dropped: bool = False
    padding_advances_from: int = -1
    no_skip: bool = False
    attn_one_short: bool = False


# Params is a plain dict: embed [V, H]; final_norm [H]; layers: list of
# dicts with mixer_norm, ffn_norm [H]; mlp = (w_gate, w_up, w_down); a
# mamba layer w_in [H, 2 di], conv_w [K, di], conv_b [di], w_x [di, R +
# 2 N], w_dt [R, di], dt_bias [di], a_log [di, N], d_skip [di], w_out
# [di, H], dt_norm [R], b_norm, c_norm [N]; an attention layer wq [H,
# heads d], wk, wv [H, kv heads d], wo [heads d, H].

FLOAT32_IN_THE_SYSTEM = ("mixer_norm", "ffn_norm", "dt_norm", "b_norm",
                         "c_norm", "conv_b", "dt_bias", "d_skip")


def selective_scan(x, delta, a, b, c, d_skip, sz: Sizes):
    """x, delta [T, di], a [di, N], b, c [T, N], d_skip [di] -> (y [T,
    di], h [di, N] after the last position): the recurrence, one
    position at a time."""
    keep = rounder(CARRY_BITS) if sz.carry_rounded else (lambda v: v)
    twice = jnp.arange(x.shape[0]) >= (
        sz.padding_advances_from if sz.padding_advances_from >= 0
        else x.shape[0])

    def one(h, args):
        x_t, dt_t, b_t, c_t, again = args
        decay = jnp.exp(dt_t[:, None] * a)
        push = (dt_t * x_t)[:, None] * b_t[None, :]
        h = keep(decay * h + push)
        h = jnp.where(again, keep(decay * h + push), h)
        y = h @ c_t
        return h, y if sz.no_skip else y + d_skip * x_t

    h, y = jax.lax.scan(one, jnp.zeros(a.shape, jnp.float32),
                        (x, delta, b, c, twice))
    return y, h


def mamba(p, u, sz: Sizes, rnd):
    """u = N1(x) [T, H] -> (the mixer's output [T, H], h [di, N] float32
    after the last position: what a server holds of this layer)."""
    t, di, n, r = u.shape[0], sz.d_inner, sz.d_state, sz.dt_rank
    xz = rnd(u @ p["w_in"])
    x, z = xz[:, :di], xz[:, di:]
    taps = sz.d_conv
    if sz.conv_tail_dropped:
        conv = p["conv_w"][taps - 1] * x
    else:
        seen = jnp.concatenate([jnp.zeros((taps - 1, di), x.dtype), x])
        conv = sum(p["conv_w"][j] * seen[j:j + t] for j in range(taps))
    x = rnd(jax.nn.silu(conv + p["conv_b"]))
    proj = rnd(x @ p["w_x"])
    dt, b, c = proj[:, :r], proj[:, r:r + n], proj[:, r + n:]
    if not sz.no_inner_norms:
        dt = rnd(rms_norm(dt, p["dt_norm"], sz.rms_norm_eps))
        b = rnd(rms_norm(b, p["b_norm"], sz.rms_norm_eps))
        c = rnd(rms_norm(c, p["c_norm"], sz.rms_norm_eps))
    delta = dt @ p["w_dt"]
    if not sz.no_dt_bias:
        delta = delta + p["dt_bias"]
    delta = jax.nn.softplus(delta)
    y, h = selective_scan(x, delta, -jnp.exp(p["a_log"]), b, c,
                          p["d_skip"], sz)
    return rnd(rnd(rnd(y) * rnd(jax.nn.silu(z))) @ p["w_out"]), h


def attention(p, u, sz: Sizes, rnd):
    """u = N1(x) [T, H] -> the mixer's output [T, H]."""
    t, d = u.shape[0], sz.head_dim
    group = sz.heads // sz.kv_heads
    q = rnd(u @ p["wq"]).reshape(t, sz.kv_heads, group, d)
    k = rnd(u @ p["wk"]).reshape(t, sz.kv_heads, d)
    v = rnd(u @ p["wv"]).reshape(t, sz.kv_heads, d)
    pos = jnp.arange(t)
    rows = min(QUERY_BLOCK, t)
    pad = -t % rows

    def some(args):
        q_b, at = args                      # [rows, G, g, d], [rows]
        s = jnp.einsum("rghd,sgd->rghs", q_b, k) / jnp.sqrt(jnp.float32(d))
        ok = (pos[None, :] < at[:, None] if sz.attn_one_short
              else pos[None, :] <= at[:, None])[:, None, None, :]
        top = jnp.max(jnp.where(ok, s, -jnp.inf), axis=-1, keepdims=True)
        e = jnp.where(ok, jnp.exp(s - jnp.where(jnp.isinf(top), 0.0, top)),
                      0.0)
        prob = rnd(e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30))
        return rnd(jnp.einsum("rghs,sgd->rghd", prob, v))

    padded = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))  # noqa: E731,E501
    cut = lambda a: a.reshape((t + pad) // rows, rows, *a.shape[1:])   # noqa: E731,E501
    o = jax.lax.map(some, (cut(padded(q)), cut(padded(pos))))
    o = o.reshape(t + pad, sz.heads * d)[:t]
    return rnd(o @ p["wo"])


def embed(params, tokens, sz: Sizes, mantissa_bits: int | None = None):
    """tokens [T] -> x [T, H]."""
    del sz
    return rounder(mantissa_bits)(
        jnp.asarray(params["embed"], jnp.float32))[tokens]


def keys_attended(lengths, sz: Sizes) -> int:
    """Keys the queries of histories of `lengths` positions attend,
    over the attention layers, as `attention`'s mask has it: the query
    at t sees positions 0 .. t (`attn_one_short`: 0 .. t - 1)."""
    total = sum(n * (n - 1) // 2 if sz.attn_one_short else n * (n + 1) // 2
                for n in map(int, lengths))
    return sz.kinds.count(ATTENTION) * total


def block_and_state(p, x, sz: Sizes, kind: str,
                    mantissa_bits: int | None = None):
    """One layer of `kind`. x [T, H] -> (x, h [di, N] float32 the
    recurrence's state after the last position; None for attention)."""
    rnd = rounder(mantissa_bits)
    with jax.default_matmul_precision("highest"):
        p = {k: (jnp.asarray(v, jnp.float32) if k in FLOAT32_IN_THE_SYSTEM
                 else jax.tree.map(
                     lambda w: rnd(jnp.asarray(w, jnp.float32)), v))
             for k, v in p.items()}
        u = rnd(rms_norm(x, p["mixer_norm"], sz.rms_norm_eps))
        out, h = (mamba(p, u, sz, rnd) if kind == MAMBA
                  else (attention(p, u, sz, rnd), None))
        x = rnd(x + out)
        y = rnd(rms_norm(x, p["ffn_norm"], sz.rms_norm_eps))
        return rnd(x + swiglu(y, p["mlp"], rnd)), h


def block(p, x, sz: Sizes, kind: str, mantissa_bits: int | None = None):
    """One layer of `kind`. x [T, H] -> x."""
    return block_and_state(p, x, sz, kind, mantissa_bits)[0]


def head(params, x, sz: Sizes, mantissa_bits: int | None = None):
    """x [T, H] -> Q [T, A] float32."""
    rnd = rounder(mantissa_bits)
    with jax.default_matmul_precision("highest"):
        x = rnd(rms_norm(x, jnp.asarray(params["final_norm"], jnp.float32),
                         sz.rms_norm_eps))
        return x @ rnd(jnp.asarray(params["embed"], jnp.float32)).T


def forward(params, tokens, sz: Sizes, mantissa_bits: int | None = None):
    """tokens [T] -> Q [T, A] float32. The pieces (`embed`, `block`,
    `head`) are public so that a caller can run them one layer at a time
    where the whole does not fit."""
    x = embed(params, tokens, sz, mantissa_bits)
    for kind, p in zip(sz.kinds, params["layers"]):
        x = block(p, x, sz, kind, mantissa_bits)
    return head(params, x, sz, mantissa_bits)
