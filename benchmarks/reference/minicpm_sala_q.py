"""Plain reference: MiniCPM-SALA's hybrid decoder (`model_type`
minicpm_sala) as a token-level Q-network, the FULL FORWARD PASS over one
whole token history in float32 `jax.numpy`, written from the catalog
row's config.json keys
(https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json),
its `described_as` ("sparse (block top-64) + lightning linear"), the
MiniCPM4 report (arXiv:2506.07900, InfLLM v2) and Lightning Attention-2
(arXiv:2401.04658) as recalled; what those leave open is marked (+) and
listed under `assumed` in
benchmarks/configs/minicpm_sala_9b_pp4_1chip.json. No cache, no chunks,
no batching, no slots, nothing imported from the system under test (the
pieces a decoder reference shares with another - RMSNorm, RoPE, SwiGLU,
the rounding to fewer bits - come from reference/glm_moe_q.py); every
entry point runs under `jax.default_matmul_precision("highest")`. The
recurrence is walked position by position (a scan that carries S), the
selection is made query by query (`QUERY_BLOCK` of them side by side:
that changes what is alive, not what is computed).

One sequence: tokens [T].

- x0 = scale_emb E[token]. Block, both kinds: x = x + r Mixer(N1(x));
  x = x + r MLP(N2(x)); RMSNorm eps; MLP = W_d[silu(W_g y) * W_u y].
  Q = (N(x) / head_divisor) W_head.
- lightning-attn: q = RoPE(n_q(y W_q)), k = RoPE(n_k(y W_k)), v = y W_v
  (n_q, n_k: RMSNorm over a head's dims, one gain vector each; RoPE
  half-split over every dim, positions 0 .. T - 1). Per head h with
  lambda_h = exp(-2^(-8 (h + 1) / heads)): S_t = lambda_h S_{t-1} +
  k_t^T v_t (float32), o_t = q_t S_t / sqrt(d). out = W_o[N_o(o) *
  sigmoid(y W_gate)], N_o an RMSNorm over all heads' channels.
- minicpm4 (no position encoding): q = n_q(y W_q), k = n_k(y W_k), v =
  y W_v; for the query at t, T = t + 1: T <= dense_len: causal softmax
  over every position, scale 1 / sqrt(d). Else compressed keys ck_j =
  mean(k[stride j : stride j + kernel]) visible when stride j + kernel
  <= T; per query head p = softmax_j(q . ck_j / sqrt(d)) over the
  visible j, summed over the heads of a key-value head's group; block
  m's score = the max of that sum over j in [per m - (kernel / stride -
  1), per m + per - 1], per = block / stride; the first init_blocks
  blocks and the window / block blocks that end at the query's own
  score +inf; the topk best blocks (the forced ones counted in it) are
  attended, one softmax over their positions <= t. out = W_o[o *
  sigmoid(y W_gate)].

`forced` [T, G, topk] (block ids, -1 for none) REPLACES a sparse
layer's selection where one is due (not the scores): at seeded weights
neighbouring block scores lie closer than bfloat16's rounding, so two
correct programs choose differently at the boundary, as two routed nets
pick different experts. `mantissa_bits`: every value the system holds
in its compute dtype is rounded to that many explicit bits (None / 23:
the reference proper), softmax, decays and S stay float32.

Departures a check must refuse, each a field of `Sizes`: `dense_always`
(dense attention where the selection is due), `decay_one` (lambda = 1),
`forced_blocks_dropped` (init and local blocks compete like the rest),
`stale_compressed` (the compressed keys of windows that closed before
the context passed dense_len were never made: zeros).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmarks.reference.glm_moe_q import (
    rms_norm, rope, rounder, swiglu)

QUERY_BLOCK = 128
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


class Sizes(NamedTuple):
    mixer_types: tuple         # one kind per layer held
    heads: int
    kv_heads: int
    head_dim: int
    lightning_heads: int
    lightning_head_dim: int
    rms_norm_eps: float
    rope_theta: float
    scale_emb: float
    residual_scale: float      # scale_depth / sqrt(published depth)
    head_divisor: float        # hidden_size / dim_model_base
    block: int
    kernel: int
    stride: int
    init_blocks: int
    window: int
    topk: int
    dense_len: int
    dense_always: bool = False
    decay_one: bool = False
    forced_blocks_dropped: bool = False
    stale_compressed: bool = False


# Params is a plain dict: embed [V, H]; final_norm [H]; head [H, V];
# layers: list of dicts with attn_norm, ffn_norm [H]; wq, w_gate
# [H, heads * d]; wk, wv [H, kv heads * d]; q_norm, k_norm [d]; wo
# [heads * d, H]; a lightning layer also o_norm [heads * d]; mlp =
# (w_gate, w_up, w_down).

FLOAT32_IN_THE_SYSTEM = ("attn_norm", "ffn_norm", "q_norm", "k_norm",
                         "o_norm")


def lightning(q, k, v, sz: Sizes):
    """q, k, v [T, H, d] -> o [T, H, d]: the recurrence, one position
    at a time."""
    heads, d = q.shape[1], q.shape[2]
    slope = jnp.exp2(-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32)
                     / heads)
    lam = jnp.ones_like(slope) if sz.decay_one else jnp.exp(-slope)

    def one(s, qkv):
        q_t, k_t, v_t = qkv
        s = lam[:, None, None] * s + k_t[:, :, None] * v_t[:, None, :]
        return s, jnp.einsum("hd,hde->he", q_t, s) / jnp.sqrt(
            jnp.float32(d))

    _, o = jax.lax.scan(one, jnp.zeros((heads, d, d), jnp.float32),
                        (q, k, v))
    return o


def compressed_keys(k, sz: Sizes, rnd):
    """k [T, G, d] -> ck [J, G, d], J = every window the T positions
    complete."""
    t = k.shape[0]
    count = max((t - sz.kernel) // sz.stride + 1, 0)
    at = (jnp.arange(count)[:, None] * sz.stride
          + jnp.arange(sz.kernel)[None, :])
    ck = rnd(k[at].mean(axis=1)) if count else jnp.zeros((0,) + k.shape[1:])
    if sz.stale_compressed:
        closed = jnp.arange(count) * sz.stride + sz.kernel
        ck = jnp.where((closed <= sz.dense_len)[:, None, None], 0.0, ck)
    return ck


def block_scores(q_t, ck, t, blocks: int, sz: Sizes):
    """One query q_t [G, g, d] at position t -> [G, blocks] float32:
    +inf forced, -inf not to be had."""
    d = q_t.shape[-1]
    per, reach = sz.block // sz.stride, sz.kernel // sz.stride - 1
    j = jnp.arange(ck.shape[0])
    seen = j * sz.stride + sz.kernel <= t + 1
    s = jnp.einsum("ghd,jgd->ghj", q_t, ck) / jnp.sqrt(jnp.float32(d))
    s = jnp.where(seen, s, -jnp.inf)
    top = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(seen, jnp.exp(s - jnp.where(jnp.isinf(top), 0.0, top)),
                  0.0)
    p = (e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30)).sum(axis=1)
    p = jnp.where(seen, p, -jnp.inf)                          # [G, J]
    m = jnp.arange(blocks)
    windows = (m[:, None] * per - reach
               + jnp.arange(per + reach)[None, :])            # [M, 5]
    there = (windows >= 0) & (windows < ck.shape[0])
    of_block = jnp.where(there[None], p[:, jnp.clip(
        windows, 0, max(ck.shape[0] - 1, 0))], -jnp.inf)
    score = of_block.max(axis=-1) if ck.shape[0] else jnp.full(
        (q_t.shape[0], blocks), -jnp.inf)
    own = t // sz.block
    if not sz.forced_blocks_dropped:
        forced = (m < sz.init_blocks) | ((m > own - sz.window // sz.block)
                                         & (m <= own))
        score = jnp.where(forced[None], jnp.inf, score)
    return jnp.where((m <= own)[None], score, -jnp.inf)


def sparse_attention(q, k, v, sz: Sizes, forced=None, rnd=lambda x: x,
                     score_at=None):
    """q [T, H, d], k, v [T, G, d] -> (o [T, H, d], own selection [T, G,
    topk] (-1: none), the block scores of the queries `score_at` [n, G,
    blocks])."""
    t, heads, d = q.shape
    g = k.shape[1]
    group = heads // g
    blocks = -(-t // sz.block)
    ck = compressed_keys(k, sz, rnd)
    pos = jnp.arange(t)
    q = q.reshape(t, g, group, d)
    short = max(sz.topk - blocks, 0)

    def one(args):
        q_t, at, forced_t = args
        score = block_scores(q_t, ck, at, blocks, sz)
        best, own = jax.lax.top_k(jnp.pad(
            score, ((0, 0), (0, short)), constant_values=-jnp.inf), sz.topk)
        own = jnp.where(jnp.isneginf(best), -1, own)
        sel = own if forced_t is None else forced_t
        chosen = (sel[:, :, None] == (pos // sz.block)[None, None, :]).any(
            axis=1)                                           # [G, T]
        dense = (at + 1 <= sz.dense_len) | sz.dense_always
        ok = jnp.where(dense, True, chosen) & (pos <= at)[None, :]
        s = jnp.einsum("ghd,sgd->ghs", q_t, k) / jnp.sqrt(jnp.float32(d))
        p = rnd(jax.nn.softmax(jnp.where(ok[:, None, :], s, -jnp.inf), -1))
        return rnd(jnp.einsum("ghs,sgd->ghd", p, v)), own, score

    rows = min(QUERY_BLOCK, t)
    pad = -t % rows

    def some(args):
        return jax.vmap(one)(args)

    padded = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))  # noqa: E731,E501
    cut = lambda a: a.reshape((t + pad) // rows, rows, *a.shape[1:])   # noqa: E731,E501
    args = (cut(padded(q)), cut(padded(pos)),
            None if forced is None else cut(padded(forced)))
    o, own, _ = jax.lax.map(some, args)
    o = o.reshape(t + pad, heads, d)[:t]
    own = own.reshape(t + pad, g, sz.topk)[:t]
    scores = None
    if score_at is not None:
        scores = jax.vmap(lambda at: block_scores(
            q[at], ck, at, blocks, sz))(jnp.asarray(score_at))
    return o, own, scores


def embed(params, tokens, sz: Sizes, mantissa_bits: int | None = None):
    """tokens [T] -> x [T, H]."""
    rnd = rounder(mantissa_bits)
    return rnd(sz.scale_emb
               * rnd(jnp.asarray(params["embed"], jnp.float32))[tokens])


def block(p, x, sz: Sizes, kind: str, forced=None,
          mantissa_bits: int | None = None, score_at=None):
    """One layer of `kind`. x [T, H] -> (x, own selection [T, G, topk],
    scores at `score_at`); a lightning layer gives None for both."""
    rnd = rounder(mantissa_bits)
    with jax.default_matmul_precision("highest"):
        p = {k: (v if k in FLOAT32_IN_THE_SYSTEM
                 else jax.tree.map(
                     lambda w: rnd(jnp.asarray(w, jnp.float32)), v))
             for k, v in p.items()}
        t = x.shape[0]
        u = rnd(rms_norm(x, p["attn_norm"], sz.rms_norm_eps))
        gate = rnd(jax.nn.sigmoid(rnd(u @ p["w_gate"])))
        own = scores = None
        if kind == LIGHTNING:
            heads, d = sz.lightning_heads, sz.lightning_head_dim
            split = lambda a: a.reshape(t, heads, d)           # noqa: E731
            q = rnd(rms_norm(split(rnd(u @ p["wq"])), p["q_norm"],
                             sz.rms_norm_eps))
            k = rnd(rms_norm(split(rnd(u @ p["wk"])), p["k_norm"],
                             sz.rms_norm_eps))
            v = split(rnd(u @ p["wv"]))
            turn = lambda a: rnd(rope(                         # noqa: E731
                a.transpose(1, 0, 2), jnp.arange(t),
                sz.rope_theta).transpose(1, 0, 2))
            o = rnd(lightning(turn(q), turn(k), v, sz)).reshape(t, -1)
            o = rnd(rms_norm(o, p["o_norm"], sz.rms_norm_eps))
        else:
            d = sz.head_dim
            q = rnd(rms_norm(rnd(u @ p["wq"]).reshape(t, sz.heads, d),
                             p["q_norm"], sz.rms_norm_eps))
            k = rnd(rms_norm(rnd(u @ p["wk"]).reshape(t, sz.kv_heads, d),
                             p["k_norm"], sz.rms_norm_eps))
            v = rnd(u @ p["wv"]).reshape(t, sz.kv_heads, d)
            o, own, scores = sparse_attention(q, k, v, sz, forced, rnd,
                                              score_at)
            o = o.reshape(t, -1)
        out = rnd(rnd(o * gate) @ p["wo"])
        x = rnd(x + rnd(sz.residual_scale * out))
        y = rnd(rms_norm(x, p["ffn_norm"], sz.rms_norm_eps))
        x = rnd(x + rnd(sz.residual_scale * swiglu(y, p["mlp"], rnd)))
    return x, own, scores


def head(params, x, sz: Sizes, mantissa_bits: int | None = None):
    """x [T, H] -> Q [T, A] float32."""
    rnd = rounder(mantissa_bits)
    with jax.default_matmul_precision("highest"):
        x = rnd(rms_norm(x, jnp.asarray(params["final_norm"], jnp.float32),
                         sz.rms_norm_eps))
        x = rnd(x / sz.head_divisor)
        return x @ rnd(jnp.asarray(params["head"], jnp.float32))


def forward(params, tokens, sz: Sizes, forced=None,
            mantissa_bits: int | None = None, score_at=None):
    """tokens [T] -> (Q [T, A] float32, own selections [sparse layers,
    T, G, topk], block scores [sparse layers, len(score_at), G,
    blocks] or None). `forced` [sparse layers, T, G, topk] replaces the
    selections. The pieces (`embed`, `block`, `head`) are public so
    that a caller can run them one layer at a time where the whole does
    not fit."""
    x = embed(params, tokens, sz, mantissa_bits)
    owns, scores = [], []
    for kind, p in zip(sz.mixer_types, params["layers"]):
        sparse = kind == SPARSE
        x, own, score = block(
            p, x, sz, kind,
            forced[len(owns)] if sparse and forced is not None else None,
            mantissa_bits, score_at if sparse else None)
        if sparse:
            owns.append(own)
            scores.append(score)
    return (head(params, x, sz, mantissa_bits), jnp.stack(owns),
            None if score_at is None else jnp.stack(scores))
