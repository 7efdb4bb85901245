"""Plain reference: Trinity-Mini's decoder (model_type afmoe) as a
token-level Q-network under the R2D2 sequence loss, in float32
`jax.numpy`, written from the model's config.json
(https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json)
and, where that file's keys leave the equations open, from the afmoe
modelling code's conventions - those are marked (+) and listed under
`assumed` in benchmarks/configs/trinity_mini_ep16_1chip.json. No
kernels, no cache, no skipping, nothing imported from the system under
test (the pieces a decoder reference shares with another - RMSNorm,
RoPE, SwiGLU, the loss, the rounding to fewer bits - come from
reference/glm_moe_q.py, whose docstring has their equations); every entry point runs under
`jax.default_matmul_precision("highest")`.

- Embedding: x0 = E[token] * sqrt(hidden) (`mup_enabled`; (+)).
- Block, (+) four norms: h = x + N2(Attn(N1(x))); y = h + N4(FFN(N3(h))).
  After the last block RMSNorm, then the untied head.
- Attention, u = N1(x): q = u W_q -> heads x d; k = u W_k, v = u W_v ->
  kv heads x d; (+) RMSNorm over the d dims of each head of q and of k
  (one gain vector each). `layer_types[i]` "sliding_attention": RoPE
  (theta, all d dims, half-split pairing, no scaling) on q and k, and
  key s is visible to query t iff 0 <= t - s < sliding_window;
  (+) "full_attention": no position encoding, visible iff s <= t.
  score = q . k / sqrt(d); query head j reads key-value head j //
  (heads / kv heads); softmax; o = sum p v; (+) o <- o * sigmoid(u
  W_gate); then W_o. The mask is built from t, s and the window and
  applied to the whole row of scores: nothing is skipped. So that it
  fits at 8,192 positions the rows are taken `QUERY_BLOCK` at a time
  (`jax.lax.map`, each block's scores recomputed in a backward pass):
  that changes what is alive, not what is computed.
- FFN: the first `dense_layers` layers one SwiGLU; the rest
  `expert_layer`, which is glm_moe_q's (sigmoid scores over ALL
  experts, top-k of s + b or of `balanced_scores`, weights the selected
  s normalised and scaled, the held experts a plain loop, one shared
  expert; in a share the routing weights carry no gradient) written
  again because that file's `balanced_scores` holds 64 experts at most.
- Loss: ONE causal pass over the whole sequence with the gradient
  stopped at the burn-in positions' keys and values (k after its norm
  and rotation, v), in every layer. That is the system's
  prefix-then-segment: a trained position depends on burn-in positions
  only through those keys and values (attention is the one place
  positions meet), their values are what a prefix pass computes
  (causality: nothing later reaches them), and a prefix pass's gradient
  is cut exactly there. And the system's trimming of a sliding layer's
  cache to its last window - 1 positions changes nothing: the first
  trained query sits at position burn_in and sees keys s > burn_in -
  window, so of the prefix only the last window - 1 keys are visible
  to ANY trained query; the mask here gives the dropped ones weight 0.

`forced_topk`, `mantissa_bits`: as in reference/glm_moe_q.py.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmarks.reference.glm_moe_q import (   # noqa: F401  (td_loss: API)
    _round_bits, rms_norm, rope, rounder, swiglu, td_loss)

QUERY_BLOCK = 512
SLIDING = "sliding_attention"


class Sizes(NamedTuple):
    heads: int
    kv_heads: int
    head_dim: int
    layer_types: tuple     # one kind per layer held
    window: int
    top_k: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    rms_norm_eps: float
    rope_theta: float
    first_expert: int      # id of the first routed expert held
    experts_held: int
    router_trains: bool    # False in a share
    forced_balance: bool = False


# Params is a plain dict:
#   embed [V, H]; final_norm [H]; head [H, V]; layers: list of dicts with
#   attn_norm, attn_out_norm, ffn_norm, ffn_out_norm [H]; wq, w_gate [H,
#   heads * d]; wk, wv [H, kv_heads * d]; q_norm, k_norm [d]; wo [heads *
#   d, H]; and either dense = (w_gate, w_up, w_down) or router [H, E],
#   router_bias [E], experts = list of (w_gate, w_up, w_down) for the
#   held ones in id order, shared = (w_gate, w_up, w_down).

FLOAT32_IN_THE_SYSTEM = ("attn_norm", "attn_out_norm", "ffn_norm",
                         "ffn_out_norm", "q_norm", "k_norm", "router",
                         "router_bias")


@jax.custom_vjp
def _round_cotangent(x, drop):
    return x


_round_cotangent.defvjp(lambda x, drop: (x, drop),
                        lambda drop, ct: (_round_bits(ct, drop), None))


def cotangent_rounder(mantissa_bits):
    """-> the identity whose COTANGENT is rounded to `mantissa_bits`
    (`rounder`'s rounding): for a value the system keeps in float32 on
    the way forward and in its compute dtype on the way back. The
    attention's scores are one: forward they stay float32 into the
    softmax, backward their cotangent ds is cast to the compute dtype
    for the two matmuls it enters (ds k and ds^T q), as every
    flash-attention backward pass does. It matters because each row of
    ds sums to exactly 0 and the keys share a large common component at
    random weights: unrounded, that component cancels; rounded, what is
    left of it is the error of the gradients that flow through the
    scores (q and k projections and head norms)."""
    if mantissa_bits is None:
        return lambda x: x
    drop = jnp.uint32(23) - jnp.asarray(mantissa_bits, jnp.uint32)
    return lambda x: _round_cotangent(x, drop)


def visible(queries, keys, window):
    """[T, S] bool from positions: causal, and inside the window if
    there is one (None: a full layer)."""
    apart = queries[:, None] - keys[None, :]
    return (apart >= 0) if window is None else (apart >= 0) & (apart < window)


def attention(p, u, sz: Sizes, kind: str, burn_in: int, rnd, window=None,
              rnd_back=lambda x: x):
    """u = N1(x) [B, T, H] -> attention output [B, T, H]. `rnd_back`:
    `cotangent_rounder` at the precision of `rnd`."""
    b, t, _ = u.shape
    d, group = sz.head_dim, sz.heads // sz.kv_heads
    pos = jnp.arange(t)
    heads_of = lambda a, n: a.reshape(b, t, n, d).transpose(0, 2, 1, 3)  # noqa: E731,E501
    q = heads_of(rnd(u @ p["wq"]), sz.heads)              # [B, h, T, d]
    k = heads_of(rnd(u @ p["wk"]), sz.kv_heads)
    v = heads_of(rnd(u @ p["wv"]), sz.kv_heads)
    q = rnd(rms_norm(q, p["q_norm"], sz.rms_norm_eps))
    k = rnd(rms_norm(k, p["k_norm"], sz.rms_norm_eps))
    sliding = kind == SLIDING
    if sliding:
        q = rnd(rope(q, pos, sz.rope_theta))
        k = rnd(rope(k, pos, sz.rope_theta))

    def cut(a):     # no gradient into the burn-in's keys and values
        return jnp.concatenate(
            [jax.lax.stop_gradient(a[:, :, :burn_in]), a[:, :, burn_in:]],
            axis=2)

    k = jnp.repeat(cut(k), group, axis=1)                 # [B, h, T, d]
    v = jnp.repeat(cut(v), group, axis=1)
    window = (sz.window if window is None else window) if sliding else None
    rows = min(QUERY_BLOCK, t)
    while t % rows:
        rows -= 1

    def some_rows(args):
        q_rows, at = args                  # [B, h, rows, d], [rows]
        scores = rnd_back(jnp.einsum("bhtd,bhsd->bhts", q_rows, k)
                          / jnp.sqrt(jnp.float32(d)))
        scores = jnp.where(visible(at, pos, window), scores, -jnp.inf)
        probs = rnd(jax.nn.softmax(scores, axis=-1))
        return rnd(jnp.einsum("bhts,bhsd->bhtd", probs, v))

    out = jax.lax.map(jax.checkpoint(some_rows), (
        jnp.moveaxis(q.reshape(b, sz.heads, t // rows, rows, d), 2, 0),
        pos.reshape(t // rows, rows)))                # [n, B, h, rows, d]
    out = jnp.moveaxis(out, 0, 2).reshape(b, sz.heads, t, d)
    out = out.transpose(0, 2, 1, 3).reshape(b, t, sz.heads * d)
    gate = rnd(jax.nn.sigmoid(rnd(u @ p["w_gate"])))
    return rnd(rnd(out * gate) @ p["wo"])


def balanced_scores(tokens, layer, experts: int):
    """tokens [B, T] (the sequence from its first position) -> [B, T,
    experts] float32, all of a token's scores distinct: glm_moe_q's
    function of the same name for any number of experts (that one
    leaves the expert's id 6 bits; 128 experts need 7). In unsigned
    32-bit arithmetic, products and sums wrapping:
        h = token * 0x9E3779B1 + position * 0x85EBCA77
            + layer * 0xC2B2AE3D + expert * 0x27D4EB2F
        h ^= h >> 16; h *= 0x85EBCA6B; h ^= h >> 13; h *= 0xC2B2AE35
        h ^= h >> 16                      (murmur3's 32-bit finalizer)
        score = (h >> (8 + b)) * 2^b + (experts - 1 - expert),
        b = max(6, bits of experts - 1)   (24 bits: exact in float32)
    `layer` counts every layer held from 0, the dense ones too; it may
    be a traced integer."""
    u = jnp.uint32
    id_bits = max((experts - 1).bit_length(), 6)
    e = jnp.arange(experts, dtype=u)
    position = jnp.arange(tokens.shape[1], dtype=u)
    h = (tokens.astype(u)[..., None] * u(0x9E3779B1)
         + position[None, :, None] * u(0x85EBCA77)
         + jnp.asarray(layer, u) * u(0xC2B2AE3D) + e * u(0x27D4EB2F))
    h = h ^ (h >> 16)
    h = h * u(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * u(0xC2B2AE35)
    h = h ^ (h >> 16)
    score = (h >> (8 + id_bits)) * u(1 << id_bits) + (u(experts - 1) - e)
    return score.astype(jnp.float32)


def expert_layer(p, x, sz: Sizes, forced, rnd, tokens=None, layer=0):
    """-> (FFN(x) [B, T, H], own top-k ids [B, T, k], gap [B, T] between
    the k-th and (k+1)-th selection scores). glm_moe_q.expert_layer's
    equations with this file's `balanced_scores`."""
    s = jax.nn.sigmoid(x @ p["router"])
    if sz.forced_balance:
        select = balanced_scores(tokens, layer, s.shape[-1])
    else:
        select = s + jax.lax.stop_gradient(p["router_bias"])
    top, own = jax.lax.top_k(select, sz.top_k + 1)
    own, gap = own[..., :sz.top_k], top[..., sz.top_k - 1] - top[..., sz.top_k]
    ids = own if forced is None else forced
    w = jnp.take_along_axis(s, ids, axis=-1)
    if sz.norm_topk_prob:
        w = w / w.sum(axis=-1, keepdims=True)
    w = w * sz.routed_scaling_factor
    if not sz.router_trains:
        w = jax.lax.stop_gradient(w)
    out = swiglu(x, p["shared"], rnd)
    for j, expert in enumerate(p["experts"]):       # the held ones only
        w_j = jnp.where(ids == sz.first_expert + j, w, 0.0).sum(axis=-1)
        out = out + rnd(rnd(w_j)[..., None] * swiglu(x, expert, rnd))
    return rnd(out), own, gap


def embed(params, tokens, mantissa_bits: int | None = None):
    """tokens [B, T] -> x [B, T, H] = E[token] * sqrt(H): the model's
    `mup_enabled` is true, and this reference is that model's."""
    rnd = rounder(mantissa_bits)
    table = rnd(jnp.asarray(params["embed"], jnp.float32))
    return rnd(table[tokens] * rnd(jnp.sqrt(jnp.float32(table.shape[1]))))


def block(p, x, sz: Sizes, burn_in: int = 0, forced=None,
          mantissa_bits: int | None = None, tokens=None, layer=0,
          kind: str | None = None, window=None):
    """One layer. `layer`: its index among those held, which enters
    `balanced_scores` (it may be traced); `kind`: its kind, by default
    `sz.layer_types[layer]`; `window`: by default `sz.window` (it may be
    traced: a caller asks what the model would give without its window
    by passing the sequence's length). x [B, T, H] -> (x, own top-k ids
    [B, T, k], gap [B, T]); a dense layer gives ids and gaps of size 0.
    `forced` [B, T, k] replaces the selection (not the scores)."""
    rnd = rounder(mantissa_bits)
    eps = sz.rms_norm_eps
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda w: jnp.asarray(w, jnp.float32), p)
        p = {k: (v if k in FLOAT32_IN_THE_SYSTEM else jax.tree.map(rnd, v))
             for k, v in p.items()}
        attn = attention(p, rnd(rms_norm(x, p["attn_norm"], eps)), sz,
                         sz.layer_types[layer] if kind is None else kind,
                         burn_in, rnd, window,
                         cotangent_rounder(mantissa_bits))
        x = rnd(x + rnd(rms_norm(attn, p["attn_out_norm"], eps)))
        y = rnd(rms_norm(x, p["ffn_norm"], eps))
        b, t = x.shape[:2]
        if "dense" in p:
            ffn = swiglu(y, p["dense"], rnd)
            own = jnp.zeros((b, t, 0), jnp.int32)
            gap = jnp.zeros((b, 0), jnp.float32)
        else:
            ffn, own, gap = expert_layer(p, y, sz, forced, rnd, tokens,
                                         layer)
        return rnd(x + rnd(rms_norm(ffn, p["ffn_out_norm"], eps))), own, gap


def head(params, x, sz: Sizes, mantissa_bits: int | None = None):
    """x [B, T, H] -> Q [B, T, A] float32."""
    rnd = rounder(mantissa_bits)
    with jax.default_matmul_precision("highest"):
        x = rnd(rms_norm(x, jnp.asarray(params["final_norm"], jnp.float32),
                         sz.rms_norm_eps))
        return x @ rnd(jnp.asarray(params["head"], jnp.float32))


def forward(params, tokens, sz: Sizes, burn_in: int = 0,
            forced_topk=None, mantissa_bits: int | None = None):
    """tokens [B, T] -> (Q [B, T, A] float32, own top-k ids [expert
    layers, B, T, k], gap [expert layers, B, T]). The pieces (`embed`,
    `block`, `head`) are public so that a caller can run them one layer
    at a time where the whole does not fit."""
    x = embed(params, tokens, mantissa_bits)
    owns, gaps = [], []
    for layer, p in enumerate(params["layers"]):
        forced = None
        if "dense" not in p and forced_topk is not None:
            forced = forced_topk[len(owns)]
        x, own, gap = block(p, x, sz, burn_in, forced, mantissa_bits,
                            tokens, layer)
        if "dense" not in p:
            owns.append(own)
            gaps.append(gap)
    q = head(params, x, sz, mantissa_bits)
    b, t = tokens.shape
    own = (jnp.stack(owns) if owns
           else jnp.zeros((0, b, t, sz.top_k), jnp.int32))
    gap = jnp.stack(gaps) if gaps else jnp.zeros((0, b, t), jnp.float32)
    return q, own, gap


def sequence_loss(online, target, tokens, actions, rewards, terminals,
                  mask, weights, *, sizes: Sizes, burn_in: int, n_step: int,
                  gamma: float, eta: float, huber_delta: float = 1.0,
                  forced_online=None, forced_target=None,
                  mantissa_bits: int | None = None):
    """tokens/actions/rewards/terminals/mask [B, L]; weights [B].
    -> (loss, {"q" [B, L - burn_in, A], "priorities" [B], "valid" and
    "td" [B, L - burn_in], "topk_online"/"topk_target" [expert layers,
    B, L, k], "gap_online"/"gap_target" [expert layers, B, L]})."""
    q, own, gap = forward(online, tokens, sizes, burn_in, forced_online,
                          mantissa_bits)
    q_t, own_t, gap_t = forward(target, tokens, sizes, burn_in,
                                forced_target, mantissa_bits)
    q, q_t = q[:, burn_in:], q_t[:, burn_in:]
    loss, aux = td_loss(
        q, q_t, *(x[:, burn_in:] for x in (actions, rewards, terminals,
                                           mask)),
        weights, n_step=n_step, gamma=gamma, eta=eta,
        huber_delta=huber_delta)
    return loss, {**aux, "q": q, "topk_online": own, "topk_target": own_t,
                  "gap_online": gap, "gap_target": gap_t}


def loss_and_gradients(online, *args, **kwargs):
    """-> ((loss, aux), d loss / d online): `jax.grad` of
    `sequence_loss` itself, every parameter of the online net."""
    return jax.value_and_grad(sequence_loss, has_aux=True)(
        online, *args, **kwargs)
