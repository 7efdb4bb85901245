"""Plain reference: GLM-4.7-Flash's decoder (model_type glm4_moe_lite)
as a token-level Q-network under the R2D2 sequence loss, in float32
`jax.numpy`, written from the model's config.json
(https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json)
and the DeepSeek-V2/V3 papers its attention and expert layers follow.
No kernels, no cache, no recomputation, nothing imported from the
system under test; every entry point runs under
`jax.default_matmul_precision("highest")`.

- RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g.
- Block: h = x + MLA(RMSNorm(x)); y = h + FFN(RMSNorm(h)). After the
  last block RMSNorm, then the untied head: Q(s_t, .) = head(norm(h_t)).
- MLA (multi-head latent attention): c_q = RMSNorm(x W_qa); q = c_q
  W_qb, per head [q_nope | q_rope]. x W_kva = [c_kv | k_r]; c_kv =
  RMSNorm(c_kv); c_kv W_kvb, per head [k_nope | v]. Rotary embedding
  (theta, every rope dim, no scaling; HALF-SPLIT pairing, dim i with
  dim i + d/2) on q_rope and on k_r, which all heads share. score =
  (q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope), causal, softmax,
  out = sum p v, then W_o. No biases.
- Expert layer: s = sigmoid(x W_g) over ALL routed experts; the top-k
  of s + b are selected (b: a fixed buffer, no gradient); the weights
  are the selected s (without b) divided by their sum, times the
  scaling factor. FFN(x) = sum_k w_k E_k(x) + E_shared(x), E(x) =
  W_down(silu(W_gate x) * W_up x). Leading layers are one dense SwiGLU.
- The share: `Sizes.first_expert`/`experts_held` say which experts'
  weights `Params` holds. The router and the normalisation are over all
  experts; only held ones add w_k E_k(x) (a plain loop over them with a
  dense mask); what the absent ones would add is left out, as in the
  system, and the partial sum goes on to the next layer. Embedding and
  head have the held vocabulary rows only. When fewer than all experts
  are held the routing weights carry no gradient (`Sizes.router_trains`
  False): the router's gradient is a sum over every selected expert's
  term, the absent experts' terms are absent, and the held experts'
  terms alone are not the router's gradient.
- Forced balanced routing (`Sizes.forced_balance`; the system's option
  of the same meaning is for measuring with random weights, where
  s + b sends nearly every token to the same k experts): the selection
  is the top-k of `balanced_scores` - a fixed pseudo-random order of
  the experts for each (token id, position, layer) - instead of the
  top-k of s + b. The weights are still the selected s, normalised and
  scaled.
- No multi-token-prediction layer (the config's
  `num_nextn_predict_layers` 1): a TD loss has no use for it.
- Loss: reference/r2d2.py's, with no stored state. There the burn-in
  is a first pass whose final state enters the trained pass with its
  gradient stopped. Here the whole sequence is ONE causal pass and the
  gradient is stopped at the burn-in positions' keys and values (c_kv
  and k_r of positions < burn_in, in every layer). That is the same
  function with the same gradient: a trained position's output depends
  on burn-in positions only through those keys and values (attention
  is the one place positions meet), their values are what a prefix
  pass computes (causality: nothing later reaches them), and a prefix
  pass's gradient is cut exactly there. Burn-in positions' own outputs
  are not in the loss.

Routing can be forced (`forced_topk`): in bfloat16 the system's scores
differ in the last bits and a near-tie flips a selection, which moves a
token's output by a whole expert. Values are therefore compared with
the reference forced to the system's selection, and the selection
itself is compared apart (`own_topk`, `gap`: the reference's own top-k
at the same inputs and the distance between its k-th and (k+1)-th
selection scores).

A lower precision, to set the comparison's limits by: `mantissa_bits`
= m rounds weights and every activation the system keeps in its compute
dtype to m explicit bits (`rounder`: `jax.lax.reduce_precision`'s
rounding, on the bits); what the system keeps in float32 (router,
softmax, norm statistics, Q) is not rounded. 7 is bfloat16. None is the
reference itself.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmarks.reference.r2d2 import huber, nstep_targets


def _round_bits(x, drop):
    """x to 23 - `drop` explicit bits of mantissa, on the bits."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    half = (jnp.uint32(1) << drop) >> jnp.uint32(1)
    odd = (bits >> drop) & jnp.uint32(1)
    kept = ((bits + half - jnp.uint32(1) + odd) >> drop) << drop
    return jnp.where(drop > 0,
                     jax.lax.bitcast_convert_type(kept, jnp.float32), x)


@jax.custom_vjp
def _round_both_ways(x, drop):
    return _round_bits(x, drop)


_round_both_ways.defvjp(
    lambda x, drop: (_round_bits(x, drop), drop),
    # a system that keeps an activation in m bits keeps its cotangent
    # in m bits too: the backward pass is rounded where the forward is
    lambda drop, ct: (_round_bits(ct, drop), None))


def rounder(mantissa_bits):
    """-> x rounded to `mantissa_bits` explicit bits of mantissa, to
    nearest, ties to even, float32's exponent kept: what
    `jax.lax.reduce_precision(x, 8, mantissa_bits)` gives for finite
    values (a test holds the two together), written on the bits so that
    `mantissa_bits` may be a traced integer and one compiled graph
    serves every precision; the identity for None (the reference
    proper) and for 23. Differentiable: the cotangent is rounded the
    same way (`_round_both_ways`), so `jax.grad` of the reference at m
    bits is the gradient a system with m bits would compute."""
    if mantissa_bits is None:
        return lambda x: x
    drop = jnp.uint32(23) - jnp.asarray(mantissa_bits, jnp.uint32)
    return lambda x: _round_both_ways(x, drop)


class Sizes(NamedTuple):
    heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    top_k: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    rms_norm_eps: float
    rope_theta: float
    first_expert: int      # id of the first routed expert held
    experts_held: int
    router_trains: bool    # False in a share: see the module docstring
    forced_balance: bool = False   # see the module docstring


# Params is a plain dict:
#   embed [V, H]; final_norm [H]; head [H, V]; layers: list of dicts with
#   attn_norm [H], wq_a [H, q_lora], q_norm [q_lora], wq_b [q_lora,
#   heads * (nope + rope)], wkv_a [H, kv_lora + rope], kv_norm
#   [kv_lora], wkv_b [kv_lora, heads * (nope + v)], wo [heads * v, H],
#   ffn_norm [H], and either dense = (w_gate, w_up, w_down) or
#   router [H, E], router_bias [E], experts = list of (w_gate, w_up,
#   w_down) for the held ones in id order, shared = (w_gate, w_up,
#   w_down).


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, positions, theta):
    """x [..., T, d] with T at axis -2 -> rotated; half-split pairing."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(x, w, rnd):
    w_gate, w_up, w_down = w
    gate, up = rnd(x @ w_gate), rnd(x @ w_up)
    return rnd(rnd(rnd(jax.nn.silu(gate)) * up) @ w_down)


def mla(p, x, sz: Sizes, burn_in: int, rnd):
    """x [B, T, H] -> attention output [B, T, H]."""
    b, t, _ = x.shape
    nope, rp, vd = sz.qk_nope_head_dim, sz.qk_rope_head_dim, sz.v_head_dim
    pos = jnp.arange(t)
    c_q = rnd(rms_norm(rnd(x @ p["wq_a"]), p["q_norm"], sz.rms_norm_eps))
    q = rnd(c_q @ p["wq_b"]).reshape(b, t, sz.heads, nope + rp)
    q = q.transpose(0, 2, 1, 3)                           # [B, h, T, d]
    q_nope, q_rope = q[..., :nope], rnd(rope(q[..., nope:], pos,
                                             sz.rope_theta))
    kv_a = rnd(x @ p["wkv_a"])
    c_kv = rnd(rms_norm(kv_a[..., :sz.kv_lora_rank], p["kv_norm"],
                        sz.rms_norm_eps))
    k_r = rnd(rope(kv_a[..., sz.kv_lora_rank:], pos, sz.rope_theta))

    def cut(a):     # no gradient into the burn-in's keys and values
        return jnp.concatenate(
            [jax.lax.stop_gradient(a[:, :burn_in]), a[:, burn_in:]], axis=1)

    c_kv, k_r = cut(c_kv), cut(k_r)
    kv = rnd(c_kv @ p["wkv_b"]).reshape(b, t, sz.heads, nope + vd)
    kv = kv.transpose(0, 2, 1, 3)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (jnp.einsum("bhtd,bhsd->bhts", q_nope, k_nope)
              + jnp.einsum("bhtd,bsd->bhts", q_rope, k_r)
              ) / jnp.sqrt(jnp.float32(nope + rp))
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = rnd(jax.nn.softmax(scores, axis=-1))
    out = rnd(jnp.einsum("bhts,bhsd->bthd", probs, v))
    return rnd(out.reshape(b, t, sz.heads * vd) @ p["wo"])


def balanced_scores(tokens, layer: int, experts: int):
    """tokens [B, T] (the sequence from its first position) -> [B, T,
    experts] float32, all of a token's scores distinct. In unsigned
    32-bit arithmetic, products and sums wrapping:
        h = token * 0x9E3779B1 + position * 0x85EBCA77
            + layer * 0xC2B2AE3D + expert * 0x27D4EB2F
        h ^= h >> 16; h *= 0x85EBCA6B; h ^= h >> 13; h *= 0xC2B2AE35
        h ^= h >> 16                      (murmur3's 32-bit finalizer)
        score = (h >> 14) * 64 + (experts - 1 - expert)
    `layer` counts every layer from 0, the dense ones too."""
    mask = 0xFFFFFFFF
    e = jnp.arange(experts, dtype=jnp.uint32)
    position = jnp.arange(tokens.shape[1], dtype=jnp.uint32)
    h = (tokens.astype(jnp.uint32)[..., None] * jnp.uint32(0x9E3779B1)
         + position[None, :, None] * jnp.uint32(0x85EBCA77)
         + jnp.uint32((layer * 0xC2B2AE3D) & mask)
         + e * jnp.uint32(0x27D4EB2F))
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    score = (h >> 14) * jnp.uint32(64) + (jnp.uint32(experts - 1) - e)
    return score.astype(jnp.float32)


def route(p, x, sz: Sizes, tokens=None, layer: int = 0):
    """x [B, T, H] -> (s [B, T, E], own top-k ids [B, T, k], gap [B, T]
    between the k-th and (k+1)-th selection scores)."""
    s = jax.nn.sigmoid(x @ p["router"])
    if sz.forced_balance:
        select = balanced_scores(tokens, layer, s.shape[-1])
    else:
        select = s + jax.lax.stop_gradient(p["router_bias"])
    top, ids = jax.lax.top_k(select, sz.top_k + 1)
    return s, ids[..., :sz.top_k], top[..., sz.top_k - 1] - top[..., sz.top_k]


def expert_layer(p, x, sz: Sizes, forced, rnd, tokens=None, layer: int = 0):
    """-> (FFN(x) [B, T, H], own top-k ids, gap)."""
    s, own, gap = route(p, x, sz, tokens, layer)
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


FLOAT32_IN_THE_SYSTEM = ("attn_norm", "q_norm", "kv_norm", "ffn_norm",
                         "router", "router_bias")


def embed(params, tokens, mantissa_bits: int | None = None):
    """tokens [B, T] -> x [B, T, H]."""
    rnd = rounder(mantissa_bits)
    return rnd(jnp.asarray(params["embed"], jnp.float32))[tokens]


def block(p, x, sz: Sizes, burn_in: int = 0, forced=None,
          mantissa_bits: int | None = None, tokens=None, layer: int = 0):
    """One layer. x [B, T, H] -> (x, own top-k ids [B, T, k], gap
    [B, T]); a dense layer gives ids and gaps of size 0. `forced`
    [B, T, k] replaces the selection (not the scores). `tokens` [B, T]
    and `layer` (this layer's index, dense layers counted) are read
    only under `sz.forced_balance`."""
    rnd = rounder(mantissa_bits)
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda w: jnp.asarray(w, jnp.float32), p)
        p = {k: (v if k in FLOAT32_IN_THE_SYSTEM else jax.tree.map(rnd, v))
             for k, v in p.items()}
        x = rnd(x + mla(p, rnd(rms_norm(x, p["attn_norm"],
                                        sz.rms_norm_eps)),
                        sz, burn_in, rnd))
        y = rnd(rms_norm(x, p["ffn_norm"], sz.rms_norm_eps))
        b, t = x.shape[:2]
        if "dense" in p:
            return (rnd(x + swiglu(y, p["dense"], rnd)),
                    jnp.zeros((b, t, 0), jnp.int32),
                    jnp.zeros((b, 0), jnp.float32))
        ffn, own, gap = expert_layer(p, y, sz, forced, rnd, tokens, layer)
        return rnd(x + ffn), own, gap


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
    layers, B, T, k], gap [expert layers, B, T]). `forced_topk` [expert
    layers, B, T, k] replaces the selection (not the scores). The
    pieces (`embed`, `block`, `head`) are public so that a caller can
    run them one layer at a time where the whole does not fit."""
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


def td_loss(q, q_t, actions, rewards, terminals, mask, weights, *,
            n_step: int, gamma: float, eta: float,
            huber_delta: float = 1.0, greedy=None):
    """The loss on the trained steps' Q-values of both nets. q, q_t
    [B, T, A]; actions/rewards/terminals/mask [B, T]; weights [B].
    `greedy` [B, T] replaces double-Q's own argmax over q, as
    `forced_topk` replaces a selection: two near-tied Q-values swap
    places in a lower precision and the bootstrap value jumps to
    another action's, which is no rounding error.
    -> (loss, {"priorities" [B], "valid" and "td" [B, T]})."""
    pick = lambda table, a: jnp.take_along_axis(     # noqa: E731
        table, a[..., None].astype(jnp.int32), axis=-1)[..., 0]
    boot = pick(q_t, jnp.argmax(q, axis=-1) if greedy is None else greedy)
    y, valid = nstep_targets(rewards, terminals, mask, boot, n_step, gamma)
    td = (pick(q, actions) - jax.lax.stop_gradient(y)) * valid
    n_valid = jnp.maximum(valid.sum(axis=1), 1.0)
    per_sequence = huber(td, huber_delta).sum(axis=1) / n_valid
    loss = jnp.mean(weights * per_sequence)
    td_abs = jnp.abs(td)
    priorities = (eta * td_abs.max(axis=1)
                  + (1.0 - eta) * td_abs.sum(axis=1) / n_valid)
    return loss, {"priorities": priorities, "valid": valid, "td": td}


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
