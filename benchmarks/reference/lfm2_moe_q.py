"""Plain reference: LFM2-24B-A2B's decoder (model_type lfm2_moe) as a
token-level Q-network under the R2D2 sequence loss, in float32
`jax.numpy`, written from the model's config.json
(https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json)
and, where that file's keys leave the equations open, from the
`lfm2_moe` family's modelling code as recalled (LFM2 technical report,
arXiv:2511.23404) - those are marked (+) and listed under `assumed` in
benchmarks/configs/lfm2_24b_ep8_1chip.json. No kernels, no cache, no
skipping, nothing imported from the system under test (the pieces a
decoder reference shares with another - RMSNorm, RoPE, SwiGLU, the
loss, the rounding to fewer bits, the balanced scores, the mask - come
from reference/glm_moe_q.py and reference/afmoe_q.py); every entry
point runs under `jax.default_matmul_precision("highest")`. H = hidden.

- Embedding: x0 = E[token], (+) no scale.
- Block: h = x + Op(N_op(x)); y = h + FFN(N_ffn(h)); after the last
  block one RMSNorm, then the head.
- Conv operator (`layer_types[i]` "conv"), u = N_op(x): [B | C | x~] =
  u W_in ((+) three blocks of H in that order); z = B * x~;
  c_t = sum_{j < K} w_j * z_{t - (K - 1) + j} WRITTEN AS THE SUM OVER
  TAPS ON A LEFT-PADDED ARRAY (zeros before the first position), one
  filter a channel, (+) no activation; Op = (C * c) W_out.
- Attention operator ("full_attention"): q, k, v = u W_q, u W_k, u W_v;
  (+) RMSNorm over the d dims of each head of q and of k (one gain
  vector each); RoPE on every dim of q and k (theta, half-split
  pairing, no scaling); causal, full; score q . k / sqrt(d); query head
  j reads key-value head j // (heads / kv heads); softmax; W_o. No
  output gate. A materialised softmax, `QUERY_BLOCK` rows of queries at
  a time (`jax.lax.map`, each block's scores recomputed in a backward
  pass): that changes what is alive, not what is computed.
- FFN: the first `dense` layers one SwiGLU; the rest the expert layer:
  s = sigmoid(x W_r) over ALL experts, top-k of s + b (or of
  `balanced_scores`), weights the selected s over their sum ((+) the
  family adds 1e-6 to that sum, the program's shared module 1e-20 and
  so does this file) x `routed_scaling_factor`, the held experts a
  plain loop, NO shared expert; in a share the weights carry no
  gradient.
- Head: Q = x E^T, whole: (+) `tie_embedding`, the head IS the
  embedding. `params["head"]` is that matrix, [A, H]; a caller that
  differentiates gives E's gradient as the sum of its two uses
  (`loss_and_gradients` does, since `forward` reads one array twice).
- Loss: ONE causal pass over the whole sequence with the gradient
  stopped where the system's prefix pass stops it: at the burn-in
  positions' z (a conv layer: of the prefix a trained position reads
  the last K - 1 rows of z and nothing else) and at their keys and
  values (an attention layer). A trained position depends on burn-in
  positions only through those, causality makes their values what a
  prefix pass computes, and a prefix pass's gradient is cut exactly
  there.

`forced_topk`, `mantissa_bits`: as in reference/glm_moe_q.py. At m bits
the reference rounds where the program holds a value in its compute
dtype (the norms' outputs, every projection's output, z, the gated C *
c, RoPE's output, the softmax's weights and their product with v, the
residual sums) and NOWHERE ELSE: the filter's taps and its sum are
float32 in the program too.

THE DEPARTURES, each a field of `Sizes` whose default is the model's
own; the cell's check has to refuse every one under "show_limits":
`conv_tail_ignored` (zeros where the burn-in's two rows of z belong:
the trained segment starts from an empty filter - this net's
`window_ignored`), `conv_out_gate_left_out` (Op = c W_out),
`conv_silu_added` (SiLU behind the filter: Kimi's and Mamba's conv
under LFM2's name), `qk_norm_left_out`, `head_untied` (the head is a
second seeded matrix, `UNTIED_SEED`, and E is the lookup alone).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmarks.reference.afmoe_q import (   # noqa: F401
    QUERY_BLOCK, balanced_scores, cotangent_rounder, visible)
from benchmarks.reference.glm_moe_q import (   # noqa: F401  (td_loss: API)
    rms_norm, rope, rounder, swiglu, td_loss)

CONV, FULL = "conv", "full_attention"
UNTIED_SEED = 0x1F2            # `head_untied`'s second matrix
INIT_STD = 0.02
WEIGHT_SUM_EPS = 1e-20


class Sizes(NamedTuple):
    layer_types: tuple     # one of "conv" / "full_attention" per layer held
    heads: int
    kv_heads: int
    head_dim: int
    top_k: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    rms_norm_eps: float
    rope_theta: float
    first_expert: int      # id of the first routed expert held
    experts_held: int
    router_trains: bool    # False in a share
    forced_balance: bool = False
    # the model's own; the other values are the departures (see above)
    conv_tail_ignored: bool = False
    conv_out_gate_left_out: bool = False
    conv_silu_added: bool = False
    qk_norm_left_out: bool = False
    head_untied: bool = False


# Params is a plain dict:
#   embed [V, H] (the head too); final_norm [H]; layers: list of dicts
#   with op_norm, ffn_norm [H] and
#   (conv) w_in [H, 3 H]; conv_w [K, H]; w_out [H, H]
#   (full_attention) wq [H, heads * d]; wk, wv [H, kv_heads * d]; q_norm,
#         k_norm [d]; wo [heads * d, H]
#   and either dense = (w_gate, w_up, w_down) or router [H, E],
#   router_bias [E], experts = list of (w_gate, w_up, w_down) for the
#   held ones in id order.

FLOAT32_IN_THE_SYSTEM = ("op_norm", "ffn_norm", "q_norm", "k_norm",
                         "router", "router_bias", "conv_w")


def _cut(a, burn_in: int, axis: int = 1):
    """No gradient into the first `burn_in` positions of `axis`."""
    lead, rest = jnp.split(a, [burn_in], axis=axis)
    return jnp.concatenate([jax.lax.stop_gradient(lead), rest], axis=axis)


def tap_sum(z, w):
    """z [B, T, H], w [K, H] -> c [B, T, H]: c_t = sum_j w_j z_{t - (K -
    1) + j}, zeros before position 0."""
    taps, t = w.shape[0], z.shape[1]
    padded = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(w[j] * padded[:, j:j + t] for j in range(taps))


def conv_operator(p, u, sz: Sizes, burn_in: int, rnd):
    """u = N_op(x) [B, T, H] -> the operator's output [B, T, H]."""
    h = u.shape[-1]
    bcx = rnd(u @ p["w_in"])
    gate_b, gate_c, x = (bcx[..., i * h:(i + 1) * h] for i in range(3))
    z = _cut(rnd(gate_b * x), burn_in)
    c = tap_sum(z, p["conv_w"])
    if sz.conv_tail_ignored and burn_in:
        c = jnp.concatenate(
            [c[:, :burn_in], tap_sum(z[:, burn_in:], p["conv_w"])], axis=1)
    if sz.conv_silu_added:
        c = jax.nn.silu(c)
    y = c if sz.conv_out_gate_left_out else gate_c * c
    return rnd(rnd(y) @ p["w_out"])


def attention(p, u, sz: Sizes, burn_in: int, rnd, rnd_back=lambda x: x):
    """u = N_op(x) [B, T, H] -> attention output [B, T, H]. `rnd_back`:
    `cotangent_rounder` at the precision of `rnd`."""
    b, t, _ = u.shape
    d, group = sz.head_dim, sz.heads // sz.kv_heads
    pos = jnp.arange(t)
    heads_of = lambda a, n: a.reshape(b, t, n, d).transpose(0, 2, 1, 3)  # noqa: E731,E501
    q = heads_of(rnd(u @ p["wq"]), sz.heads)              # [B, h, T, d]
    k = heads_of(rnd(u @ p["wk"]), sz.kv_heads)
    v = heads_of(rnd(u @ p["wv"]), sz.kv_heads)
    if not sz.qk_norm_left_out:
        q = rnd(rms_norm(q, p["q_norm"], sz.rms_norm_eps))
        k = rnd(rms_norm(k, p["k_norm"], sz.rms_norm_eps))
    q = rnd(rope(q, pos, sz.rope_theta))
    k = rnd(rope(k, pos, sz.rope_theta))
    # no gradient into the burn-in's keys and values
    k = jnp.repeat(_cut(k, burn_in, 2), group, axis=1)    # [B, h, T, d]
    v = jnp.repeat(_cut(v, burn_in, 2), group, axis=1)
    rows = min(QUERY_BLOCK, t)
    while t % rows:
        rows -= 1

    def some_rows(args):
        q_rows, at = args                  # [B, h, rows, d], [rows]
        scores = rnd_back(jnp.einsum("bhtd,bhsd->bhts", q_rows, k)
                          / jnp.sqrt(jnp.float32(d)))
        scores = jnp.where(visible(at, pos, None), scores, -jnp.inf)
        probs = rnd(jax.nn.softmax(scores, axis=-1))
        return rnd(jnp.einsum("bhts,bhsd->bhtd", probs, v))

    out = jax.lax.map(jax.checkpoint(some_rows), (
        jnp.moveaxis(q.reshape(b, sz.heads, t // rows, rows, d), 2, 0),
        pos.reshape(t // rows, rows)))                # [n, B, h, rows, d]
    out = jnp.moveaxis(out, 0, 2).reshape(b, sz.heads, t, d)
    out = out.transpose(0, 2, 1, 3).reshape(b, t, sz.heads * d)
    return rnd(out @ p["wo"])


def dense_ffn(y, w, rnd):
    """(silu(y W_gate) * (y W_up)) W_down; the program holds the gated
    product once."""
    w_gate, w_up, w_down = w
    gate, up = rnd(y @ w_gate), rnd(y @ w_up)
    return rnd(rnd(jax.nn.silu(gate) * up) @ w_down)


def expert_layer(p, x, sz: Sizes, forced, rnd, tokens=None, layer=0):
    """-> (FFN(x) [B, T, H], own top-k ids [B, T, k], gap [B, T] between
    the k-th and (k+1)-th selection scores). No shared expert."""
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
        w = w / (w.sum(axis=-1, keepdims=True) + WEIGHT_SUM_EPS)
    w = w * sz.routed_scaling_factor
    if not sz.router_trains:
        w = jax.lax.stop_gradient(w)
    out = jnp.zeros_like(x)
    for j, expert in enumerate(p["experts"]):       # the held ones only
        w_j = jnp.where(ids == sz.first_expert + j, w, 0.0).sum(axis=-1)
        out = out + rnd(rnd(w_j)[..., None] * swiglu(x, expert, rnd))
    return rnd(out), own, gap


def embed(params, tokens, mantissa_bits: int | None = None):
    """tokens [B, T] -> x [B, T, H] = E[token]."""
    rnd = rounder(mantissa_bits)
    return rnd(jnp.asarray(params["embed"], jnp.float32))[tokens]


def block(p, x, sz: Sizes, burn_in: int = 0, forced=None,
          mantissa_bits: int | None = None, tokens=None, layer=0,
          kind: str | None = None, window=None):
    """One layer, reference/afmoe_q.block's signature (`window` is taken
    for it and ignored: no layer of this model has one). x [B, T, H] ->
    (x, own top-k ids [B, T, k], gap [B, T]); a dense layer gives ids
    and gaps of size 0. `forced` [B, T, k] replaces the selection (not
    the scores); `layer` may be traced, `kind` (by default
    `sz.layer_types[layer]`) is static."""
    del window
    rnd = rounder(mantissa_bits)
    eps = sz.rms_norm_eps
    kind = sz.layer_types[layer] if kind is None else kind
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda w: jnp.asarray(w, jnp.float32), p)
        p = {k: (v if k in FLOAT32_IN_THE_SYSTEM else jax.tree.map(rnd, v))
             for k, v in p.items()}
        u = rnd(rms_norm(x, p["op_norm"], eps))
        if kind == CONV:
            mixed = conv_operator(p, u, sz, burn_in, rnd)
        else:
            mixed = attention(p, u, sz, burn_in, rnd,
                              cotangent_rounder(mantissa_bits))
        x = rnd(x + mixed)
        y = rnd(rms_norm(x, p["ffn_norm"], eps))
        b, t = x.shape[:2]
        if "dense" in p:
            return (rnd(x + dense_ffn(y, p["dense"], rnd)),
                    jnp.zeros((b, t, 0), jnp.int32),
                    jnp.zeros((b, 0), jnp.float32))
        ffn, own, gap = expert_layer(p, y, sz, forced, rnd, tokens, layer)
        return rnd(x + ffn), own, gap


def head(params, x, sz: Sizes, mantissa_bits: int | None = None):
    """x [B, T, H] -> Q [B, T, A] float32 = N(x) E^T, `params["head"]`
    [A, H] being the embedding itself."""
    rnd = rounder(mantissa_bits)
    with jax.default_matmul_precision("highest"):
        x = rnd(rms_norm(x, jnp.asarray(params["final_norm"], jnp.float32),
                         sz.rms_norm_eps))
        matrix = jnp.asarray(params["head"], jnp.float32)
        if sz.head_untied:
            matrix = INIT_STD * jax.random.normal(
                jax.random.key(UNTIED_SEED), matrix.shape, jnp.float32)
        return x @ rnd(matrix).T


def forward(params, tokens, sz: Sizes, burn_in: int = 0,
            forced_topk=None, mantissa_bits: int | None = None):
    """tokens [B, T] -> (Q [B, T, A] float32, own top-k ids [expert
    layers, B, T, k], gap [expert layers, B, T]). The pieces (`embed`,
    `block`, `head`) are public so that a caller can run them one layer
    at a time where the whole does not fit. `params` has no `head`: the
    head reads `params["embed"]`."""
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
    q = head({"final_norm": params["final_norm"], "head": params["embed"]},
             x, sz, mantissa_bits)
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
    `sequence_loss` itself, every parameter of the online net; `embed`'s
    is the sum of the lookup's and the head's."""
    return jax.value_and_grad(sequence_loss, has_aux=True)(
        online, *args, **kwargs)
