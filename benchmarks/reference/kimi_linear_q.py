"""Plain reference: Kimi-Linear-48B-A3B-Instruct's decoder as a
token-level Q-network under the R2D2 sequence loss, in float32
`jax.numpy`, written from the model's config.json
(https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json,
`model_type` kimi_linear) and, where that file's keys leave the
equations open, from the Kimi Linear report (arXiv:2510.26692) - those
are marked (+) and listed under `assumed` in
benchmarks/configs/kimi_linear_48b_ep32_1chip.json. No kernels, no
chunks, no cache, nothing imported from the system under test (the
pieces a decoder reference shares with another - RMSNorm, RoPE, the
loss, the rounding to fewer bits - come from reference/glm_moe_q.py; the
mask, the expert layer with its forced selection's scores and the
cotangent's rounding from reference/afmoe_q.py); every entry point runs
under `jax.default_matmul_precision("highest")`.

- Embedding: x0 = E[token], no scale. No bias anywhere.
- Block: h = x + Mixer(N1(x)); y = h + FFN(N2(h)); after the last block
  RMSNorm (eps 1e-5), then the untied head. `Sizes.layer_types` names
  each layer's mixer, "kda" or "mla"; a layer whose parameters hold
  `dense` has one SwiGLU for its FFN, every other the expert layer.
- KDA (Kimi Delta Attention; heads x d), on u = N1(x): q~, k~, v~ = u
  W_q, u W_k, u W_v, each through a causal depthwise convolution over
  time (kernel K = 4, one filter a channel, zeros before position 0:
  out_t = sum_j w_j x_{t - (K - 1) + j}) and SiLU; (+) q = q~ /
  sqrt(|q~|^2 + 1e-6) / sqrt(d), k likewise without the 1 / sqrt(d),
  per head; g_t = -exp(A_log_h) softplus(u_t W_f_down W_f_up + dt_bias)
  per head and KEY CHANNEL; beta_t = sigmoid(u_t W_beta) per head;
  THE RECURRENCE, ONE POSITION AT A TIME IN A `lax.scan`, from S = 0:
      S <- Diag(exp g_t) S;  S <- S - beta_t k_t (k_t^T S) + beta_t k_t v_t^T
      o_t = S^T q_t
  y_t = [RMSNorm_head(o_t; o_norm) * sigmoid(u_t W_g_down W_g_up)] W_o.
  So that a backward pass through 8,192 positions does not keep S_t for
  every t (17 GiB), the scan runs in segments of `SEGMENT` positions
  under `jax.checkpoint`: that changes what is alive, not what is
  computed.
- MLA: q = u W_q -> heads x (nope + rope) (no low rank); u W_kva ->
  [c_kv | k_r], c_kv = RMSNorm(c_kv); c_kv W_kvb -> heads x [k_nope |
  v]; k = [k_nope | k_r], k_r shared by all heads; NO ROTATION; score =
  q . k / sqrt(nope + rope), causal, softmax, W_o. The rows are taken
  `QUERY_BLOCK` at a time, each block's scores materialised whole.
- Expert layer: reference/afmoe_q.expert_layer (sigmoid scores, top-k of
  score + fixed bias or of `balanced_scores`, weights normalised and
  scaled, held experts a plain loop, one shared expert; in a share the
  weights carry no gradient).
- Loss: ONE causal pass over the whole sequence with the gradient
  stopped where the system's prefix pass stops it: in a KDA layer at S
  after the last burn-in position and at the burn-in positions' rows of
  the three PRE-convolution streams (what the trained positions'
  convolutions still read); in an MLA layer at the burn-in positions'
  keys and values AFTER W_kvb (the system's blockwise attention takes
  the prefix's expanded keys and values as constants: models/mla.py).

FOUR DEPARTURES A CHECK MUST TELL APART (`Sizes.decay_per_head`,
`.short_conv`, `.mla_rotated`, `.gate`; the defaults are the model):
one decay a head (the mean of its channels' g: Gated DeltaNet under
Kimi's name), the short convolution left out, RoPE (theta 10,000, the
file's unused `rope_theta`) on the MLA layer's 64 shared dims, and SiLU
for the output gate's sigmoid. The cell's check holds the system
against each and every one has to come out NOT correct.

`forced_topk`, `mantissa_bits`: as in reference/glm_moe_q.py. At m bits
the reference rounds where the program holds a value in its compute
dtype (the norms' outputs, every projection's output, SiLU's, the gated
output, the residual sums) and NOWHERE ELSE: q and k after their L2
norm, g, beta, S and o are float32 in the program too.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmarks.reference.afmoe_q import (   # noqa: F401
    QUERY_BLOCK, balanced_scores, cotangent_rounder, expert_layer, visible)
from benchmarks.reference.glm_moe_q import (   # noqa: F401  (td_loss: API)
    rms_norm, rope, rounder, swiglu, td_loss)

SEGMENT = 64          # positions of the recurrence a checkpoint spans
L2_EPS = 1e-6
ROTATED_THETA = 10_000.0     # `mla_rotated`'s: the file's own rope_theta


class Sizes(NamedTuple):
    layer_types: tuple     # one of "kda" / "mla" per layer held
    kda_heads: int
    kda_head_dim: int
    heads: int             # MLA's
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    top_k: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    rms_norm_eps: float
    first_expert: int      # id of the first routed expert held
    experts_held: int
    router_trains: bool    # False in a share
    forced_balance: bool = False
    # the model's own; the other values are the departures (see above)
    decay_per_head: bool = False
    short_conv: bool = True
    mla_rotated: bool = False
    gate: str = "sigmoid"                   # | "silu"


# Params is a plain dict:
#   embed [V, H]; final_norm [H]; head [H, V]; layers: list of dicts with
#   attn_norm, ffn_norm [H] and
#   (kda) wq, wk, wv [H, heads * d]; conv_q, conv_k, conv_v [K, heads * d];
#         a_log [heads]; dt_bias [heads * d]; wf_a [H, d]; wf_b [d, heads *
#         d]; w_beta [H, heads]; wg_a [H, d]; wg_b [d, heads * d]; o_norm
#         [d]; wo [heads * d, H]
#   (mla) wq [H, heads * (nope + rope)]; wkv_a [H, kv_lora + rope];
#         kv_norm [kv_lora]; wkv_b [kv_lora, heads * (nope + v)]; wo
#         [heads * v, H]
#   and either dense = (w_gate, w_up, w_down) or router [H, E],
#   router_bias [E], experts = list of (w_gate, w_up, w_down) for the
#   held ones in id order, shared = (w_gate, w_up, w_down).

FLOAT32_IN_THE_SYSTEM = ("attn_norm", "ffn_norm", "kv_norm", "router",
                         "router_bias", "conv_q", "conv_k", "conv_v",
                         "a_log", "dt_bias", "o_norm")


def _cut(a, burn_in: int, axis: int = 1):
    """No gradient into the first `burn_in` positions of `axis`."""
    lead, rest = jnp.split(a, [burn_in], axis=axis)
    return jnp.concatenate([jax.lax.stop_gradient(lead), rest], axis=axis)


def delta_rule(q, k, v, g, beta, state):
    """q, k, g [B, T, h, d]; v [B, T, h, dv]; beta [B, T, h]; state [B,
    h, d, dv] -> (o [B, T, h, dv], state after position T - 1): the
    recurrence of the module docstring, one position at a time."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[..., None] * s
        s = (s - b_t[..., None, None] * k_t[..., None]
             * jnp.einsum("bhk,bhkv->bhv", k_t, s)[..., None, :]
             + b_t[..., None, None] * k_t[..., None] * v_t[..., None, :])
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    t = q.shape[1]
    if t == 0:
        return jnp.zeros(v.shape, v.dtype), state
    seg = max(n for n in range(1, min(SEGMENT, t) + 1) if t % n == 0)
    xs = tuple(jnp.moveaxis(x, 1, 0).reshape(t // seg, seg, *x.shape[:1],
                                             *x.shape[2:])
               for x in (q, k, v, g, beta))
    state, o = jax.lax.scan(
        jax.checkpoint(lambda s, x: jax.lax.scan(step, s, x)), state, xs)
    return jnp.moveaxis(o.reshape(t, *o.shape[2:]), 0, 1), state


def kda(p, u, sz: Sizes, burn_in: int, rnd):
    """u = N1(x) [B, T, H] -> the mixer's output [B, T, H]."""
    b, t, _ = u.shape
    h, d = sz.kda_heads, sz.kda_head_dim
    per_head = lambda a: a.reshape(b, t, h, d)            # noqa: E731

    def stream(name):
        x = _cut(rnd(u @ p["w" + name]), burn_in)
        if sz.short_conv:
            w = p["conv_" + name]
            taps = w.shape[0]
            before = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
            x = sum(w[j] * before[:, j:j + t] for j in range(taps))
        return per_head(rnd(jax.nn.silu(x)))

    unit = lambda a: a / jnp.sqrt(                        # noqa: E731
        jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)
    q = unit(stream("q")) / jnp.sqrt(jnp.float32(d))
    k, v = unit(stream("k")), stream("v")
    g = -jnp.exp(p["a_log"])[:, None] * per_head(jax.nn.softplus(
        rnd(u @ p["wf_a"]) @ p["wf_b"] + p["dt_bias"]))
    if sz.decay_per_head:
        g = jnp.broadcast_to(g.mean(axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(u @ p["w_beta"])
    parts = [a[:, :burn_in] for a in (q, k, v, g, beta)]
    rest = [a[:, burn_in:] for a in (q, k, v, g, beta)]
    o_b, state = delta_rule(*parts, jnp.zeros((b, h, d, d), jnp.float32))
    o_t, _ = delta_rule(*rest, jax.lax.stop_gradient(state))
    o = jnp.concatenate([o_b, o_t], axis=1)
    o = rms_norm(o, p["o_norm"], sz.rms_norm_eps)
    act = {"sigmoid": jax.nn.sigmoid, "silu": jax.nn.silu}[sz.gate]
    gate = act(rnd(u @ p["wg_a"]) @ p["wg_b"])
    return rnd(rnd(o.reshape(b, t, h * d) * gate) @ p["wo"])


def mla(p, u, sz: Sizes, burn_in: int, rnd, rnd_back=lambda x: x):
    """u = N1(x) [B, T, H] -> attention output [B, T, H]."""
    b, t, _ = u.shape
    nope, rp, vd = sz.qk_nope_head_dim, sz.qk_rope_head_dim, sz.v_head_dim
    pos = jnp.arange(t)
    q = rnd(u @ p["wq"]).reshape(b, t, sz.heads, nope + rp)
    q = q.transpose(0, 2, 1, 3)                           # [B, h, T, d]
    kv_a = rnd(u @ p["wkv_a"])
    c_kv = rnd(rms_norm(kv_a[..., :sz.kv_lora_rank], p["kv_norm"],
                        sz.rms_norm_eps))
    k_r = kv_a[..., sz.kv_lora_rank:]                     # [B, T, rope]
    if sz.mla_rotated:
        q = jnp.concatenate(
            [q[..., :nope], rnd(rope(q[..., nope:], pos, ROTATED_THETA))],
            axis=-1)
        k_r = rnd(rope(k_r, pos, ROTATED_THETA))
    kv = rnd(c_kv @ p["wkv_b"]).reshape(b, t, sz.heads, nope + vd)
    kv = kv.transpose(0, 2, 1, 3)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_r[:, None], (b, sz.heads, t, rp))], axis=-1)
    # no gradient into the burn-in's keys and values, cut AFTER W_kvb
    k, v = _cut(k, burn_in, 2), _cut(kv[..., nope:], burn_in, 2)
    rows = min(QUERY_BLOCK, t)
    while t % rows:
        rows -= 1

    def some_rows(args):
        q_rows, at = args                  # [B, h, rows, d], [rows]
        scores = rnd_back(jnp.einsum("bhtd,bhsd->bhts", q_rows, k)
                          / jnp.sqrt(jnp.float32(nope + rp)))
        scores = jnp.where(visible(at, pos, None), scores, -jnp.inf)
        probs = rnd(jax.nn.softmax(scores, axis=-1))
        return rnd(jnp.einsum("bhts,bhsd->bhtd", probs, v))

    out = jax.lax.map(jax.checkpoint(some_rows), (
        jnp.moveaxis(q.reshape(b, sz.heads, t // rows, rows, nope + rp),
                     2, 0),
        pos.reshape(t // rows, rows)))                # [n, B, h, rows, vd]
    out = jnp.moveaxis(out, 0, 2).reshape(b, sz.heads, t, vd)
    out = out.transpose(0, 2, 1, 3).reshape(b, t, sz.heads * vd)
    return rnd(out @ p["wo"])


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
        u = rnd(rms_norm(x, p["attn_norm"], eps))
        if kind == "kda":
            mixed = kda(p, u, sz, burn_in, rnd)
        else:
            mixed = mla(p, u, sz, burn_in, rnd,
                        cotangent_rounder(mantissa_bits))
        x = rnd(x + mixed)
        y = rnd(rms_norm(x, p["ffn_norm"], eps))
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
