"""Plain reference: Ouro-2.6B's looped decoder as a token-level
Q-network under the R2D2 sequence loss, in float32 `jax.numpy`, written
from the model's config.json
(https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json,
`model_type` ouro) and, where that file's keys leave the equations
open, from the family's modelling code - those are marked (+) and
listed under `assumed` in benchmarks/configs/ouro_2p6b_1chip.json. No
kernels, no cache, no skipping, no scan, nothing imported from the
system under test (the pieces a decoder reference shares with another -
RMSNorm, RoPE, SwiGLU, the loss, the rounding to fewer bits - come from
reference/glm_moe_q.py; the mask and the cotangent's rounding from
reference/afmoe_q.py, whose docstrings have their equations); every
entry point runs under `jax.default_matmul_precision("highest")`.

- Embedding: h0 = E[token], no scale. No bias in a block.
- A PYTHON LOOP OVER THE LOOP STEPS AND, INSIDE IT, OVER THE LAYERS:
  for t = 1..T: x = h^{t-1}; for l = 1..L:
      x = x + N2_l(Attn_l(N1_l(x)));  x = x + N4_l(MLP_l(N3_l(x)))
  ((+) four norms a block); (+) h^t = N_f(x), the final norm INSIDE the
  loop, its output what the next step starts from; Q = h^T W_head. The
  same L parameter dicts are read in every step: `jax.grad` of this
  function gives each weight the SUM of its T applications' gradients,
  which is what the system's one leaf has to equal.
- Attention, u = N1(x): q, k, v = u W_q, u W_k, u W_v -> heads x d each
  (ungrouped: query head j reads key-value head j // (heads / kv
  heads), a group of 1 at the published sizes); (+) no q/k norms; RoPE
  (theta, all d dims, half-split pairing, no scaling) on q and k with
  the sequence's own positions, THE SAME IN EVERY LOOP STEP; key s is
  visible to query t iff s <= t; score = q . k / sqrt(d); softmax;
  o = sum p v; then W_o. The [T, S] scores of every head are
  materialised (rows `QUERY_BLOCK` at a time through `jax.lax.map`,
  which changes what is alive, not what is computed).
- MLP(y) = (silu(y W_gate) * (y W_up)) W_down (reference/glm_moe_q's
  `swiglu`).
- (+) The exit gate is no part of Q at the published
  `early_exit_threshold` 1 (every step runs, the last step's state is
  the output), so the reference has none: its two parameters get no
  gradient, and the check holds the system's to zero.
- Loss: ONE causal pass over the whole sequence with the gradient
  stopped at the burn-in positions' keys and values (k after its
  rotation, v), IN EVERY LOOP STEP AND LAYER - reference/afmoe_q.py's
  docstring says why that is the system's prefix-then-segment: step t
  at layer l attends to the keys and values that step t, layer l made.

FOUR DEPARTURES A CHECK MUST TELL APART (`Sizes.loop_steps`,
`.prefix_from`, `.final_norm`, `.post_norms`; the defaults are the
model): fewer loop steps; step t reading STEP 0's keys and values at
the burn-in positions (one cache per layer shared by the steps - the
bug a looped model with a prefix cache invites); the final norm applied
once after the loop instead of inside it; no norm after a sublayer.
benchmarks/harness/looped_sequence_checks.py holds the system against
each and every one has to come out NOT correct.

`mantissa_bits`: as in reference/glm_moe_q.py.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmarks.reference.afmoe_q import (
    QUERY_BLOCK, cotangent_rounder, visible)
from benchmarks.reference.glm_moe_q import (   # noqa: F401  (td_loss: API)
    rms_norm, rope, rounder, swiglu, td_loss)


class Sizes(NamedTuple):
    heads: int
    kv_heads: int
    head_dim: int
    rms_norm_eps: float
    rope_theta: float
    # the model's own; the other values are the departures (see above)
    loop_steps: int = 4
    prefix_from: str = "own_step"       # | "step_0"
    final_norm: str = "inside_loop"     # | "after_loop"
    post_norms: bool = True


# Params is a plain dict:
#   embed [V, H]; final_norm [H]; head [H, V]; layers: list of dicts with
#   attn_norm, attn_out_norm, ffn_norm, ffn_out_norm [H]; wq [H, heads *
#   d]; wk, wv [H, kv_heads * d]; wo [heads * d, H]; mlp = (w_gate, w_up,
#   w_down).

FLOAT32_IN_THE_SYSTEM = ("attn_norm", "attn_out_norm", "ffn_norm",
                         "ffn_out_norm")


def attention(p, u, sz: Sizes, burn_in: int, rnd, rnd_back=lambda x: x,
              prefix=None):
    """u = N1(x) [B, T, H] -> (attention output [B, T, H], this
    application's (k, v) [B, kv heads, burn_in, d] at the burn-in
    positions). `prefix`: another application's, read in place of this
    one's at those positions (the `prefix_from` departure); `rnd_back`:
    `cotangent_rounder` at the precision of `rnd`."""
    b, t, _ = u.shape
    d, group = sz.head_dim, sz.heads // sz.kv_heads
    pos = jnp.arange(t)
    heads_of = lambda a, n: a.reshape(b, t, n, d).transpose(0, 2, 1, 3)  # noqa: E731,E501
    q = rnd(rope(heads_of(rnd(u @ p["wq"]), sz.heads), pos, sz.rope_theta))
    k = rnd(rope(heads_of(rnd(u @ p["wk"]), sz.kv_heads), pos,
                 sz.rope_theta))
    v = heads_of(rnd(u @ p["wv"]), sz.kv_heads)
    own = (k[:, :, :burn_in], v[:, :, :burn_in])
    before = own if prefix is None else prefix

    def cut(a, a_before):   # no gradient into the burn-in's keys and values
        return jnp.concatenate(
            [jax.lax.stop_gradient(a_before), a[:, :, burn_in:]], axis=2)

    k = jnp.repeat(cut(k, before[0]), group, axis=1)      # [B, h, T, d]
    v = jnp.repeat(cut(v, before[1]), group, axis=1)
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
    return rnd(out @ p["wo"]), own


def embed(params, tokens, mantissa_bits: int | None = None):
    """tokens [B, T] -> h0 [B, T, H] = E[token]."""
    rnd = rounder(mantissa_bits)
    return rnd(jnp.asarray(params["embed"], jnp.float32))[tokens]


def block(p, x, sz: Sizes, burn_in: int = 0,
          mantissa_bits: int | None = None, prefix=None):
    """One application of one block. x [B, T, H] -> (x, this
    application's (k, v) at the burn-in positions)."""
    rnd = rounder(mantissa_bits)
    eps = sz.rms_norm_eps
    after = ((lambda a, g: rnd(rms_norm(a, g, eps))) if sz.post_norms
             else (lambda a, g: a))
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda w: jnp.asarray(w, jnp.float32), p)
        p = {k: (v if k in FLOAT32_IN_THE_SYSTEM else jax.tree.map(rnd, v))
             for k, v in p.items()}
        attn, kv = attention(
            p, rnd(rms_norm(x, p["attn_norm"], eps)), sz, burn_in, rnd,
            cotangent_rounder(mantissa_bits), prefix)
        h = rnd(x + after(attn, p["attn_out_norm"]))
        ffn = swiglu(rnd(rms_norm(h, p["ffn_norm"], eps)), p["mlp"], rnd)
        return rnd(h + after(ffn, p["ffn_out_norm"])), kv


def end_of_step(params, x, sz: Sizes, step: int,
                mantissa_bits: int | None = None):
    """The stream at the end of loop step `step` (0-based) -> what the
    next step, or the head, starts from: N_f(x) inside the loop; under
    the `after_loop` departure x itself until the last step."""
    if sz.final_norm == "after_loop" and step != sz.loop_steps - 1:
        return x
    rnd = rounder(mantissa_bits)
    return rnd(rms_norm(x, jnp.asarray(params["final_norm"], jnp.float32),
                        sz.rms_norm_eps))


def head(params, h, sz: Sizes, mantissa_bits: int | None = None):
    """h = the last step's state [B, T, H] -> Q [B, T, A] float32."""
    del sz
    rnd = rounder(mantissa_bits)
    with jax.default_matmul_precision("highest"):
        return h @ rnd(jnp.asarray(params["head"], jnp.float32))


def forward(params, tokens, sz: Sizes, burn_in: int = 0,
            mantissa_bits: int | None = None, layers_of_step=None):
    """tokens [B, T] -> Q [B, T, A] float32. The pieces (`embed`,
    `block`, `end_of_step`, `head`) are public so that a caller can run
    them one application at a time where the whole does not fit.
    `layers_of_step(t)` -> the layer dicts step t reads (by default the
    same `params["layers"]` every step: the model); a test passes
    separate copies to see each application's own gradient."""
    x = embed(params, tokens, mantissa_bits)
    first = {}
    for step in range(sz.loop_steps):
        layers = (params["layers"] if layers_of_step is None
                  else layers_of_step(step))
        for index, p in enumerate(layers):
            x, kv = block(p, x, sz, burn_in, mantissa_bits,
                          first.get(index) if sz.prefix_from == "step_0"
                          else None)
            first.setdefault(index, kv)
        x = end_of_step(params, x, sz, step, mantissa_bits)
    return head(params, x, sz, mantissa_bits)


def sequence_loss(online, target, tokens, actions, rewards, terminals,
                  mask, weights, *, sizes: Sizes, burn_in: int, n_step: int,
                  gamma: float, eta: float, huber_delta: float = 1.0,
                  mantissa_bits: int | None = None, layers_of_step=None):
    """tokens/actions/rewards/terminals/mask [B, L]; weights [B].
    -> (loss, {"q" [B, L - burn_in, A], "priorities" [B], "valid" and
    "td" [B, L - burn_in]}). `layers_of_step`: the ONLINE net's, see
    `forward`."""
    q = forward(online, tokens, sizes, burn_in, mantissa_bits,
                None if layers_of_step is None
                else lambda t: layers_of_step(online, t))
    q_t = forward(target, tokens, sizes, burn_in, mantissa_bits)
    q, q_t = q[:, burn_in:], q_t[:, burn_in:]
    loss, aux = td_loss(
        q, q_t, *(x[:, burn_in:] for x in (actions, rewards, terminals,
                                           mask)),
        weights, n_step=n_step, gamma=gamma, eta=eta,
        huber_delta=huber_delta)
    return loss, {**aux, "q": q}


def loss_and_gradients(online, *args, **kwargs):
    """-> ((loss, aux), d loss / d online): `jax.grad` of
    `sequence_loss` itself, every parameter of the online net."""
    return jax.value_and_grad(sequence_loss, has_aux=True)(
        online, *args, **kwargs)
