"""Plain reference: SmallThinker-21BA3B-Instruct's decoder as a
token-level Q-network under the R2D2 sequence loss, in float32
`jax.numpy`, written from the model's config.json
(https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json)
and, where that file's keys leave the equations open, from the family's
modelling code and the catalog row's description - those are marked (+)
and listed under `assumed` in
benchmarks/configs/smallthinker_21b_ep8_1chip.json. No kernels, no
cache, no skipping, nothing imported from the system under test (the
pieces a decoder reference shares with another - RMSNorm, RoPE, the
loss, the rounding to fewer bits - come from reference/glm_moe_q.py;
the mask, the forced selection's scores and the cotangent's rounding
from reference/afmoe_q.py, whose docstrings have their equations);
every entry point runs under `jax.default_matmul_precision("highest")`.

- Embedding: x0 = E[token], no scale. (+) No bias anywhere.
- Block, two norms: u = N1(x); (+) r = u W_router (the router reads the
  attention's normed input, AHEAD of attention); h = x + Attn(u);
  y = h + MoE(N2(h); r). After the last block RMSNorm, then the untied
  head.
- Attention: q = u W_q -> heads x d; k = u W_k, v = u W_v -> kv heads x
  d; no head norms, no output gate. A "sliding_attention" layer
  (`sliding_window_layout` and `rope_layout` 1): RoPE (theta, all d
  dims, half-split pairing, no scaling) on q and k, and key s is
  visible to query t iff 0 <= t - s < window ((+) the window counts the
  query); a "full_attention" layer (both 0): no position encoding,
  visible iff s <= t. score = q . k / sqrt(d); query head j reads
  key-value head j // (heads / kv heads); softmax; o = sum p v; then
  W_o. The mask is built from t, s and the window and applied to the
  whole row of scores: nothing is skipped. So that it fits at 16,384
  positions the rows are taken `QUERY_BLOCK` at a time (`jax.lax.map`,
  each block's scores recomputed in a backward pass): that changes what
  is alive, not what is computed.
- MoE(z; r): ids = top-k of r (or of `balanced_scores`); weights =
  softmax over the k SELECTED logits of r; expert e =
  (relu(z W_gate_e) * (z W_up_e)) W_down_e ((+) ReGLU); the held
  experts a plain loop; no shared expert; in a share the weights carry
  no gradient.
- Loss: ONE causal pass over the whole sequence with the gradient
  stopped at the burn-in positions' keys and values in every layer -
  reference/afmoe_q.py's docstring says why that is the system's
  prefix-then-segment and why trimming a sliding layer's cache changes
  nothing.

THREE DEPARTURES A CHECK MUST TELL APART (`Sizes.activation`,
`.router_reads`, `.weights`; the defaults are the model): SiLU for
ReLU, the router fed from N2(h) (the rows the experts are fed) instead
of N1(x), and sigmoid scores divided by their sum for the softmax over
the selected. benchmarks/harness/decoder_sequence_checks.py holds the
system against each and every one has to come out NOT correct.

`forced_topk`, `mantissa_bits`: as in reference/glm_moe_q.py.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmarks.reference.afmoe_q import (   # noqa: F401  (SLIDING: API)
    QUERY_BLOCK, SLIDING, balanced_scores, cotangent_rounder, visible)
from benchmarks.reference.glm_moe_q import (   # noqa: F401  (td_loss: API)
    rms_norm, rope, rounder, td_loss)


class Sizes(NamedTuple):
    heads: int
    kv_heads: int
    head_dim: int
    layer_types: tuple     # one kind per layer held
    window: int
    top_k: int
    rms_norm_eps: float
    rope_theta: float
    first_expert: int      # id of the first routed expert held
    experts_held: int
    router_trains: bool    # False in a share
    forced_balance: bool = False
    # the model's own; the other values are the departures (see above)
    activation: str = "relu"                # | "silu"
    router_reads: str = "attention_input"   # | "expert_input"
    weights: str = "softmax_selected"       # | "sigmoid_normalised"


# Params is a plain dict:
#   embed [V, H]; final_norm [H]; head [H, V]; layers: list of dicts with
#   attn_norm, ffn_norm [H]; wq [H, heads * d]; wk, wv [H, kv_heads * d];
#   wo [heads * d, H]; router [H, E]; experts = list of (w_gate, w_up,
#   w_down) for the held ones in id order.

FLOAT32_IN_THE_SYSTEM = ("attn_norm", "ffn_norm", "router")


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
    return rnd(out @ p["wo"])


def gated_unit(z, w, sz: Sizes, rnd):
    """One expert: (act(z W_gate) * (z W_up)) W_down."""
    w_gate, w_up, w_down = w
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[sz.activation]
    gate, up = rnd(z @ w_gate), rnd(z @ w_up)
    return rnd(rnd(rnd(act(gate)) * up) @ w_down)


def expert_layer(p, z, read, sz: Sizes, forced, rnd, tokens=None, layer=0):
    """z [B, T, H] the rows the experts are fed, `read` [B, T, H] the
    tensor the router reads -> (MoE [B, T, H], own top-k ids [B, T, k],
    gap [B, T] between the k-th and (k+1)-th selection scores)."""
    r = read @ p["router"]
    select = (balanced_scores(tokens, layer, r.shape[-1])
              if sz.forced_balance else r)
    top, own = jax.lax.top_k(select, sz.top_k + 1)
    own, gap = own[..., :sz.top_k], top[..., sz.top_k - 1] - top[..., sz.top_k]
    ids = own if forced is None else forced
    chosen = jnp.take_along_axis(r, ids, axis=-1)
    if sz.weights == "softmax_selected":
        w = jax.nn.softmax(chosen, axis=-1)
    else:
        s = jax.nn.sigmoid(chosen)
        w = s / s.sum(axis=-1, keepdims=True)
    if not sz.router_trains:
        w = jax.lax.stop_gradient(w)
    out = jnp.zeros_like(z)
    for j, expert in enumerate(p["experts"]):       # the held ones only
        w_j = jnp.where(ids == sz.first_expert + j, w, 0.0).sum(axis=-1)
        out = out + rnd(rnd(w_j)[..., None] * gated_unit(z, expert, sz, rnd))
    return rnd(out), own, gap


def embed(params, tokens, mantissa_bits: int | None = None):
    """tokens [B, T] -> x [B, T, H] = E[token]."""
    rnd = rounder(mantissa_bits)
    return rnd(jnp.asarray(params["embed"], jnp.float32))[tokens]


def block(p, x, sz: Sizes, burn_in: int = 0, forced=None,
          mantissa_bits: int | None = None, tokens=None, layer=0,
          kind: str | None = None, window=None):
    """One layer, reference/afmoe_q.block's signature. x [B, T, H] ->
    (x, own top-k ids [B, T, k], gap [B, T]). `forced` [B, T, k]
    replaces the selection (not the logits)."""
    rnd = rounder(mantissa_bits)
    eps = sz.rms_norm_eps
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda w: jnp.asarray(w, jnp.float32), p)
        p = {k: (v if k in FLOAT32_IN_THE_SYSTEM else jax.tree.map(rnd, v))
             for k, v in p.items()}
        u = rnd(rms_norm(x, p["attn_norm"], eps))
        h = rnd(x + attention(
            p, u, sz, sz.layer_types[layer] if kind is None else kind,
            burn_in, rnd, window, cotangent_rounder(mantissa_bits)))
        z = rnd(rms_norm(h, p["ffn_norm"], eps))
        read = u if sz.router_reads == "attention_input" else z
        ffn, own, gap = expert_layer(p, z, read, sz, forced, rnd, tokens,
                                     layer)
        return rnd(h + ffn), own, gap


def head(params, x, sz: Sizes, mantissa_bits: int | None = None):
    """x [B, T, H] -> Q [B, T, A] float32."""
    rnd = rounder(mantissa_bits)
    with jax.default_matmul_precision("highest"):
        x = rnd(rms_norm(x, jnp.asarray(params["final_norm"], jnp.float32),
                         sz.rms_norm_eps))
        return x @ rnd(jnp.asarray(params["head"], jnp.float32))


def forward(params, tokens, sz: Sizes, burn_in: int = 0,
            forced_topk=None, mantissa_bits: int | None = None):
    """tokens [B, T] -> (Q [B, T, A] float32, own top-k ids [layers, B,
    T, k], gap [layers, B, T]). The pieces (`embed`, `block`, `head`)
    are public so that a caller can run them one layer at a time where
    the whole does not fit."""
    x = embed(params, tokens, mantissa_bits)
    owns, gaps = [], []
    for layer, p in enumerate(params["layers"]):
        x, own, gap = block(
            p, x, sz, burn_in,
            None if forced_topk is None else forced_topk[layer],
            mantissa_bits, tokens, layer)
        owns.append(own)
        gaps.append(gap)
    return head(params, x, sz, mantissa_bits), jnp.stack(owns), jnp.stack(gaps)


def sequence_loss(online, target, tokens, actions, rewards, terminals,
                  mask, weights, *, sizes: Sizes, burn_in: int, n_step: int,
                  gamma: float, eta: float, huber_delta: float = 1.0,
                  forced_online=None, forced_target=None,
                  mantissa_bits: int | None = None):
    """tokens/actions/rewards/terminals/mask [B, L]; weights [B].
    -> (loss, {"q" [B, L - burn_in, A], "priorities" [B], "valid" and
    "td" [B, L - burn_in], "topk_online"/"topk_target" [layers, B, L,
    k], "gap_online"/"gap_target" [layers, B, L]})."""
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
