"""Ouro-2.6B's looped decoder as a token-level Q-network of the sequence
family, the fourth decoder kind beside models/glm_moe_q.py,
models/afmoe_q.py and models/smallthinker_q.py, and the first WITHOUT
an expert layer: tokens in, Q(s_t, .) = the model's own untied head
over the whole vocabulary.

    apply(params, tokens[B, T] int32, state) -> (q[B, T, A] f32, state)

THE WHOLE STACK IS RUN `total_ut_steps` TIMES WITH THE SAME WEIGHTS
(a `jax.lax.scan` over the loop steps that carries the residual stream;
the stack's parameters are closed over, so each weight is ONE gradient
leaf that receives the sum over its applications, and the compiled
program holds one pass of the L blocks, not `total_ut_steps` of them).
The L blocks inside a step are a Python loop over per-layer parameter
dicts, as in the family's other nets (PERF.md section 4 says why not a
second scan over stacked parameters).

`state` is a cache PER (LOOP STEP, LAYER): a tuple over the layers of
`(k [steps, B, C, heads, d], v, seen)` - loop step t at layer l attends
to the keys and values that step t, layer l made at the earlier
positions, never to another step's, so the scan takes `k[t]`, `v[t]` as
its input and gives the extended ones as its output. `seen` (int32
scalar) is how many positions came before; `()` is no cache. R2D2's
burn-in (ops/losses.make_r2d2_loss, unedited) is a prefix pass that
leaves it; the loss stops its gradient. Nothing is stored with a
sequence.

The equations (benchmarks/reference/ouro_q.py writes them again in
float32, independently). What the catalog's config.json keys state is
as published; what the family's modelling code adds beyond them is
marked (+) and listed under `assumed` in the benchmark's configuration
file.

- RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g, statistics in
  float32; no bias in a block. Embedding: h0 = E[token], no scale.
- Loop step t = 1..T, x = h^{t-1}; block l = 1..L, (+) four norms, one
  before and one after each sublayer:
      x = x + N2_l(Attn_l(N1_l(x)))
      x = x + N4_l(MLP_l(N3_l(x)))
  (+) h^t = N_f(x): the final norm is applied INSIDE the loop and the
  normed state is what the next step starts from. Q = h^T W_head.
- Attention: q, k, v = u W_q, u W_k, u W_v (hidden -> heads x d each;
  as many key-value heads as query heads), (+) no q/k norms; RoPE on
  every dim of q and k (theta, half-split pairing, no scaling;
  positions run on across the prefix and are THE SAME in every loop
  step: there is no step embedding); causal softmax(q k / sqrt(d)) v in
  float32; then W_o. models/windowed_gqa.py makes the call (every layer
  a full one) over ops/blockwise_attention.py.
- MLP(y) = (silu(y W_gate) * (y W_up)) W_down.
- (+) The exit gate lambda_t = sigmoid(w_g . h^t + b_g) is evaluated at
  every step on `stop_gradient(h^t)` and FEEDS A COUNTER ONLY
  (`stats["exit_gates"]`; runtime/family reports the exit
  distribution's mass on the last step): at the published
  `early_exit_threshold` 1 it stops nothing, every step runs and the
  last step's state is the output, so its 2,049 parameters get no
  gradient from this loss. A depth chosen per token at run time is not
  built (ROADMAP "Reach").

Recomputation: every block application is a `jax.checkpoint` that keeps
nothing, so what a step saves is the blocks' inputs, [T, L, tokens,
hidden] in the compute dtype. EVERY VALUE THE BLOCK ROUNDS TO THE
COMPUTE DTYPE IS ROUNDED BY `_held` (a `reduce_precision` before the
cast: norms' outputs, the seven products, RoPE's, silu's and the gated
product's, both residual sums, the embedding), so that the forward pass
and the recomputation round at the same places whatever XLA fuses:
`_held` says what a rounding left to `astype` cost this net's q and k
projections.

Scopes: `ouro.embed`, `ouro.loop` around the scan, and inside it
`afmoe.attn` (projections, RoPE, the shared attention call - which
opens `afmoe.attn.full` -, output projection), `ouro.mlp`, `ouro.norms`
(the four of a block, the loop's final norm and the gate);
`ouro.head`.

The inference server's protocol is the family's stateless window
(runtime/family.server_apply_fn); no benchmark cell drives it.
Parameters are float32, cast to the compute dtype at use; a plain
pytree (`embed_tokens`, `layers`, `norm`, `early_exit_gate`,
`lm_head`).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ape_x_dqn_tpu.models import windowed_gqa
from ape_x_dqn_tpu.models.base import dtype_of
from ape_x_dqn_tpu.models.expert_layer import (
    _rope, count_params, seeded_params)
from ape_x_dqn_tpu.ops.blockwise_attention import BLOCK_K, BLOCK_Q


def _held(x32: jax.Array, dt) -> jax.Array:
    """float32 -> the compute dtype, ROUNDED HERE WHATEVER READS IT.
    A plain `astype` is a rounding XLA may take back: where the reader
    converts to float32 again (a norm, a residual add, RoPE, silu) it
    fuses producer and reader and the value is never rounded
    (`xla_allow_excess_precision`), and it decides so apart for the
    forward pass and for a `jax.checkpoint`'s recomputation. The
    forward pass then runs on from an unrounded stream while the
    backward pass recomputes from the rounded block input it saved, and
    in this net - a stream that is one shared vector plus a little of
    the token, looped - that is most of the error of the q and k
    projections' gradients in the later loop steps (on the v5e layer
    0's k_proj 3.44 units of bfloat16's own error, 1.18 with excess
    precision off for that program; PERF.md section 6, PR 41).
    `reduce_precision` is a rounding XLA keeps, and its cotangent is
    rounded the same way. float32 compute: the value as it is."""
    if dt == jnp.float32:
        return x32
    info = jnp.finfo(dt)
    return jax.lax.reduce_precision(x32, info.nexp, info.nmant).astype(dt)


def _norm(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    """expert_layer._rms_norm with its output held."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return _held(x32 * jax.lax.rsqrt(var + eps) * g, x.dtype)


def _dot(a: jax.Array, w: jax.Array) -> jax.Array:
    """a @ w in a's dtype, float32 accumulation, the product held."""
    return _held(jnp.dot(a, w.astype(a.dtype),
                         preferred_element_type=jnp.float32), a.dtype)


def _add(x: jax.Array, y: jax.Array) -> jax.Array:
    """The residual stream's sum, held."""
    return _held(x.astype(jnp.float32) + y.astype(jnp.float32), x.dtype)


class OuroQNet:
    """The net as a value: `init(key, tokens, state)` and
    `apply(params, tokens, state)`; `s` is a configs.OuroConfig."""

    def __init__(self, s: Any, compute_dtype: str = "bfloat16",
                 expert_exchange: bool = False,
                 attn_blocks: tuple[int, int] = (BLOCK_Q, BLOCK_K)):
        """`expert_exchange`: taken for the family's constructor call
        and ignored (there is no expert layer to exchange);
        `attn_blocks`: as AfmoeQNet's."""
        del expert_exchange
        if s.early_exit_threshold != 1.0:
            raise NotImplementedError(
                f"network.ouro.early_exit_threshold="
                f"{s.early_exit_threshold}: only the published 1.0 is "
                f"built (every loop step runs; a depth chosen per token "
                f"at run time is ROADMAP's)")
        self.s = s
        self.compute_dtype = compute_dtype
        self.attn_blocks = attn_blocks
        self.num_actions = s.vocab_size

    # -- parameters --------------------------------------------------------

    def param_shapes(self) -> dict:
        """The parameter pytree as shapes (matrices are [in, out])."""
        s, h = self.s, self.s.hidden_size
        q_out = s.num_attention_heads * s.head_dim
        kv_out = s.num_key_value_heads * s.head_dim
        layer = {
            "input_layernorm": (h,),
            "q_proj": (h, q_out), "k_proj": (h, kv_out),
            "v_proj": (h, kv_out), "o_proj": (q_out, h),
            "post_attention_layernorm": (h,),
            "pre_mlp_layernorm": (h,), "post_mlp_layernorm": (h,),
            "mlp": {"gate_proj": (h, s.intermediate_size),
                    "up_proj": (h, s.intermediate_size),
                    "down_proj": (s.intermediate_size, h)}}
        return {"embed_tokens": (self.num_actions, h),
                "layers": [layer] * s.num_hidden_layers,
                "norm": (h,),
                "early_exit_gate": {"weight": (h, 1), "bias": (1,)},
                "lm_head": (h, self.num_actions)}

    def param_count(self) -> int:
        return count_params(self.param_shapes())

    def step_transient_bytes(self, batch_size: int,
                             trained_steps: int) -> int:
        """What a train step holds beside the persistent state (16 B a
        parameter), for the HBM fits-check: the float32 gradient, three
        float32 [tokens, actions] arrays (the two nets' Q-values and
        the head's cotangent - the largest action set the learner has
        had), the blocks' saved inputs [steps, layers, tokens, hidden],
        the prefix caches of two nets (priced at a prefix as long as
        the trained segment: the method is not told the burn-in), and a
        block's working set. Anchor (PR 41, published widths, 5 layers,
        batch 1 x 3,072 trained after 1,024; PERF.md section 4):
        compiled for a described v5e the step's temp is 5.05 GiB, this
        gives 4.95 (4 layers: 4.17 / 4.52; 6: 5.92 / 5.37 - the
        compiled temp grows 0.88 GiB a layer, of which the gradient is
        0.19)."""
        s = self.s
        tokens = batch_size * trained_steps
        applications = s.total_ut_steps * s.num_hidden_layers
        two = 2    # bytes of a compute-dtype value
        logits = 3 * tokens * self.num_actions * 4
        boundaries = applications * tokens * s.hidden_size * two
        caches = (2 * applications * 2 * tokens
                  * s.num_key_value_heads * s.head_dim * two)
        block = tokens * (8 * s.hidden_size + 3 * s.intermediate_size) * 4
        return (4 * self.param_count() + logits + boundaries + caches
                + block)

    def init(self, key: jax.Array, tokens: Any = None,
             state: Any = None) -> dict:
        """Seeded float32 parameters (expert_layer.seeded_params), the
        gate's bias 0. `tokens`/`state` are taken for flax's call shape
        and ignored."""
        del tokens, state
        params = seeded_params(self.param_shapes(), key)
        gate = params["early_exit_gate"]
        gate["bias"] = jnp.zeros_like(gate["bias"])
        return params

    # -- the layers --------------------------------------------------------

    def _attention(self, p: dict, u: jax.Array, cache, positions):
        """u = N1(x) [B, T, hidden] -> (attention output [B, T, hidden],
        this (step, layer)'s (k, v) with the new positions)."""
        s, dt = self.s, u.dtype
        b, t, _ = u.shape

        def heads(w, n):
            return _dot(u, w).reshape(b, t, n, s.head_dim)

        def rotated(x):
            return _held(_rope(x.astype(jnp.float32), positions,
                               s.rope_theta), dt)

        q = rotated(heads(p["q_proj"], s.num_attention_heads))
        k = rotated(heads(p["k_proj"], s.num_key_value_heads))
        v = heads(p["v_proj"], s.num_key_value_heads)
        # no q/k norms and no embedding scale, and from the first block
        # on the stream is a sum of normed sublayer outputs: the rows
        # share one large vector, so the backward pass takes each row's
        # delta from its own weights (ops/blockwise_attention.py,
        # `recompute_delta`; `about_mean`, SmallThinkerQNet's way, left
        # the q and k projections of some layers worse: PERF.md 6)
        out = windowed_gqa.attend(q, k, v, cache, None, self.attn_blocks,
                                  recompute_delta=True)
        kv = windowed_gqa.extend(cache, k, v, None)
        return _dot(out.reshape(b, t, -1), p["o_proj"]), kv

    def _block(self, p: dict, x: jax.Array, cache, positions):
        """One application of one block -> (x, its (k, v))."""
        eps = self.s.rms_norm_eps
        with jax.named_scope("ouro.norms"):
            u = _norm(x, p["input_layernorm"], eps)
        with jax.named_scope("afmoe.attn"):
            attn, kv = self._attention(p, u, cache, positions)
        with jax.named_scope("ouro.norms"):
            x = _add(x, _norm(attn, p["post_attention_layernorm"], eps))
            y = _norm(x, p["pre_mlp_layernorm"], eps)
        with jax.named_scope("ouro.mlp"):
            ffn = self._mlp(p["mlp"], y)
        with jax.named_scope("ouro.norms"):
            x = _add(x, _norm(ffn, p["post_mlp_layernorm"], eps))
        return x, kv

    @staticmethod
    def _mlp(p: dict, y: jax.Array) -> jax.Array:
        """(silu(y W_gate) * (y W_up)) W_down, every product held."""
        f32 = jnp.float32
        gate, up = _dot(y, p["gate_proj"]), _dot(y, p["up_proj"])
        act = _held(jax.nn.silu(gate.astype(f32)), y.dtype)
        return _dot(_held(act.astype(f32) * up.astype(f32), y.dtype),
                    p["down_proj"])

    def _end_of_step(self, params: dict, x: jax.Array):
        """The stream after a step's last block -> (h^t = N_f(x), what
        the next step or the head starts from; lambda_t [B, T])."""
        with jax.named_scope("ouro.norms"):
            h = _norm(x, params["norm"], self.s.rms_norm_eps)
            gate = params["early_exit_gate"]
            lam = jax.nn.sigmoid(
                jax.lax.stop_gradient(h).astype(jnp.float32)
                @ gate["weight"] + gate["bias"])[..., 0]
        return h, lam

    def _loop_step(self, params: dict, positions, carry, caches):
        """One pass of the whole stack: (h^{t-1}, blocks applied so far),
        this step's caches [layers] of (k, v) or None -> ((h^t, blocks
        applied), (this step's extended caches, lambda_t))."""
        x, applied = carry
        kvs = []
        for layer, p in enumerate(params["layers"]):
            x, kv = jax.checkpoint(self._block)(
                p, x, None if caches is None else caches[layer], positions)
            kvs.append(kv)
            applied = applied + 1        # counted where it is applied
        h, lam = self._end_of_step(params, x)
        return (h, applied), (tuple(kvs), lam)

    def _head(self, params: dict, h: jax.Array) -> jax.Array:
        """The last step's state -> Q [B, T, A] float32."""
        with jax.named_scope("ouro.head"):
            return jnp.dot(h, params["lm_head"].astype(h.dtype),
                           preferred_element_type=jnp.float32)

    # -- entry points ------------------------------------------------------

    def apply_with_stats(self, params: dict, tokens: jax.Array,
                         state: Any = ()):
        """-> (q [B, T, A] float32, state, stats):
        `stats["block_applications"]` int32, the blocks applied in this
        forward pass (steps x layers), `stats["exit_gates"]` [steps, B,
        T] float32, lambda_t at every position."""
        s = self.s
        if tokens.shape[1] > s.max_position_embeddings:
            raise ValueError(
                f"{tokens.shape[1]} tokens in one pass, but "
                f"network.ouro.max_position_embeddings="
                f"{s.max_position_embeddings}")
        dt = dtype_of(self.compute_dtype)
        tokens = tokens.astype(jnp.int32)
        seen, positions = windowed_gqa.positions_after(
            state[0] if state else None, tokens.shape[1])
        with jax.named_scope("ouro.embed"):
            x = _held(params["embed_tokens"][tokens], dt)
        with jax.named_scope("ouro.loop"):
            (x, applied), (kvs, gates) = jax.lax.scan(
                lambda carry, caches: self._loop_step(
                    params, positions, carry, caches),
                (x, jnp.int32(0)),
                tuple(c[:2] for c in state) if state else None,
                length=s.total_ut_steps)
        q = self._head(params, x)
        new_state = tuple((k, v, seen + tokens.shape[1]) for k, v in kvs)
        return q, new_state, {"block_applications": applied,
                              "exit_gates": gates}

    def apply(self, params: dict, tokens: jax.Array, state: Any = ()):
        q, state, _ = self.apply_with_stats(params, tokens, state)
        return q, state
