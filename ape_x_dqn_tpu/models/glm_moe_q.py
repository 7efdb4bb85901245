"""GLM-4.7-Flash's decoder (model_type glm4_moe_lite) as a token-level
Q-network of the sequence family: tokens in, Q(s_t, .) = the model's own
untied head over the vocabulary rows held here.

    apply(params, tokens[B, T] int32, state) -> (q[B, T, A] f32, state)

`state` is the latent cache of the positions already seen: per layer
`(c_kv [B, S, kv_lora_rank], k_rope [B, S, qk_rope_head_dim])`, or `()`
for none (S = 0). The new tokens take positions S .. S + T - 1 and
attend to the cache and, causally, to each other; the state returned
holds S + T positions. That is what makes R2D2's burn-in
(ops/losses.make_r2d2_loss, unedited) a prefix pass here: the prefix
leaves a latent cache, the loss stops its gradient, and the trained
segment attends to it. Nothing is stored with a sequence.

The equations (benchmarks/reference/glm_moe_q.py writes them again in
float32, independently):

- RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g, statistics in float32.
- Block: h = x + MLA(RMSNorm(x)); y = h + FFN(RMSNorm(h)). After the
  last block RMSNorm, then the head.
- MLA (models/mla.py, shared with models/kimi_linear_q.py; here with
  the low-rank query, RoPE and the scores materialised):
  c_q = RMSNorm(x W_qa); q = c_q W_qb -> heads x [q_nope | q_rope].
  x W_kva -> [c_kv | k_r]; c_kv = RMSNorm(c_kv); c_kv W_kvb -> heads x
  [k_nope | v]. RoPE (theta, every rope dim, no scaling, HALF-SPLIT
  pairing: dim i rotates with dim i + d/2, HF's `rotate_half`) on
  q_rope and on k_r, which all heads share. score = (q_nope . k_nope +
  q_rope . k_r) / sqrt(nope + rope), causal, softmax in float32, out =
  sum p v -> W_o. No biases. The cache holds c_kv after its norm and
  k_r after its rotation.
- Expert layer: models/expert_layer.py's routed + shared expert layer
  (both decoder nets call it; its docstring has the equations, the
  share, what a share without the exchange does to its router, the
  forced balanced selection and how the grouped matmuls run) at this
  model's numbers: top-`num_experts_per_tok` of sigmoid score + a fixed
  bias, weights normalised (`norm_topk_prob`) and times
  `routed_scaling_factor`. The first `first_k_dense_replace` layers are
  one dense SwiGLU instead.
- The share (GlmMoeConfig.shard_count / shard_index): only selected
  experts held here add their part. Embedding and head hold
  vocab_size / shard_count rows; ids, Q-values, argmax and the loss
  are over that slice.
- The config's one multi-token-prediction layer is NOT built: it serves
  the next-token likelihood in pre-training and speculation in serving;
  a TD loss has neither (HF's modelling code skips those weights too).

Layers are a Python loop, not a `lax.scan` over stacked parameters.
The scan was tried (PR 30): it compiles in 55 s instead of 87 and its
executable is a third the size, but XLA moves the float32 ->
compute-dtype cast of the stacked expert weights in front of the loop
(an `optimization_barrier` on the sliced layer did not hold it back)
and keeps a bfloat16 copy of all of them per net: temp 6.2 GiB against
2.5, compiled for a described v5e, which no longer fits the chip.

Recomputation: every block is a `jax.checkpoint`, on by the family (at
these widths a block's activations are what does not fit), so a
differentiated pass keeps one [B, T, hidden] per block and recomputes
the rest in the backward pass - all but THE SELECTION
(expert_layer.SELECTION, whose docstring says what went wrong while the
recomputation decided it again).

Parameters are float32 and cast to the compute dtype at use; a plain
pytree under HF's names (`init`: `embed_tokens`, `layers` a list of
per-layer dicts, `norm`, `lm_head`), not a flax module: `init`/`apply`
have flax's call shape, which is all runtime/family.py asks of a net.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from ape_x_dqn_tpu.models.base import dtype_of
# `_dispatch`, `_combine`: the layer's two custom VJPs, under the names
# they had here (a benchmark test reaches `glm_moe_q._combine`)
from ape_x_dqn_tpu.models.expert_layer import (  # noqa: F401
    SELECTION, ExpertShare, _balanced_scores, _combine, _dispatch, _rms_norm,
    _swiglu, count_params, expert_ffn, seeded_params)
from ape_x_dqn_tpu.models.mla import MlaSizes, mla

STEP_REST = 1 << 30      # a step beside gradients and logits (see below)


class GlmMoeQNet:
    """The net as a value: `init(key, tokens, state)` and
    `apply(params, tokens, state)`; `g` is a configs.GlmMoeConfig."""

    def __init__(self, g: Any, compute_dtype: str = "bfloat16",
                 expert_exchange: bool = False):
        """`expert_exchange`: whether the chips that share a layer
        exchange tokens and sum the router's gradient
        (parallel.mesh.has_expert_exchange; models.build_network passes
        it). A whole layer needs none."""
        if g.n_group != 1 or g.topk_group != 1:
            raise NotImplementedError(
                "network.glm: only n_group = topk_group = 1 is built "
                "(no group stage in the expert selection)")
        self.g = g
        self.compute_dtype = compute_dtype
        self.num_actions = g.vocab_size // g.shard_count
        self.experts_held = g.n_routed_experts // g.shard_count
        self.first_expert = g.shard_index * self.experts_held
        self.q_head_dim = g.qk_nope_head_dim + g.qk_rope_head_dim
        self.mla_sizes = MlaSizes(
            heads=g.num_attention_heads, nope=g.qk_nope_head_dim,
            rope=g.qk_rope_head_dim, v_dim=g.v_head_dim,
            kv_rank=g.kv_lora_rank, eps=g.rms_norm_eps,
            rope_theta=g.rope_theta)
        self.num_dense_layers = min(g.first_k_dense_replace,
                                    g.num_hidden_layers)
        self.num_moe_layers = g.num_hidden_layers - self.num_dense_layers
        self.router_trains = g.shard_count == 1 or expert_exchange
        self.share = ExpertShare(
            experts=g.n_routed_experts, top_k=g.num_experts_per_tok,
            held=self.experts_held, first=self.first_expert,
            norm_topk=g.norm_topk_prob, scale=g.routed_scaling_factor,
            router_trains=self.router_trains)

    # -- parameters --------------------------------------------------------

    def param_shapes(self) -> dict:
        """The parameter pytree as shapes (HF's names; matrices are
        [in, out], a layer's held experts stacked on a leading axis)."""
        g, h = self.g, self.g.hidden_size
        heads = g.num_attention_heads

        def ffn(width, lead=()):
            return {"gate_proj": (*lead, h, width),
                    "up_proj": (*lead, h, width),
                    "down_proj": (*lead, width, h)}

        def attention():
            return {
                "input_layernorm": (h,),
                "q_a_proj": (h, g.q_lora_rank),
                "q_a_layernorm": (g.q_lora_rank,),
                "q_b_proj": (g.q_lora_rank, heads * self.q_head_dim),
                "kv_a_proj_with_mqa": (
                    h, g.kv_lora_rank + g.qk_rope_head_dim),
                "kv_a_layernorm": (g.kv_lora_rank,),
                "kv_b_proj": (g.kv_lora_rank, heads * (
                    g.qk_nope_head_dim + g.v_head_dim)),
                "o_proj": (heads * g.v_head_dim, h),
                "post_attention_layernorm": (h,),
            }

        moe = {
            "gate": (h, g.n_routed_experts),
            "e_score_correction_bias": (g.n_routed_experts,),
            "experts": ffn(g.moe_intermediate_size, (self.experts_held,)),
            "shared_experts": ffn(
                g.moe_intermediate_size * g.n_shared_experts)}
        layers = [{**attention(), "mlp": ffn(g.intermediate_size)}
                  for _ in range(self.num_dense_layers)]
        layers += [{**attention(), "mlp": moe}
                   for _ in range(self.num_moe_layers)]
        return {"embed_tokens": (self.num_actions, h), "layers": layers,
                "norm": (h,), "lm_head": (h, self.num_actions)}

    def param_count(self) -> int:
        return count_params(self.param_shapes())

    def step_transient_bytes(self, batch_size: int,
                             trained_steps: int) -> int:
        """What a train step holds beside the persistent state (16 B a
        parameter), for the HBM fits-check: this net's learner state
        fills the chip, so its step is priced and not taken as noise
        beside the replay. The gradients (4 B a parameter, in flight
        between backward and optimizer), the loss's three float32
        arrays [batch, trained steps, vocabulary held] (online Q,
        target Q, the gradient of the first) and STEP_REST. Anchors
        (PR 30, published widths, 1 + 4 layers, batch 16 x 512; PERF.md
        sections 4 and 6): compiled for a described v5e the step's temp
        is 2.43 GiB (gradients and logits overlap in time, so their
        sum, 3.5, is high) and the inference server's own float32 copy
        of the parameters, which nothing prices, is 2.2 more: 14.1 GiB
        in all against the 13.97 this gives; the chip's allocator
        reported a peak of 13.02."""
        logits = batch_size * trained_steps * self.num_actions * 4
        return 4 * self.param_count() + 3 * logits + STEP_REST

    def init(self, key: jax.Array, tokens: Any = None,
             state: Any = None) -> dict:
        """Seeded float32 parameters (expert_layer.seeded_params).
        `tokens`/`state` are taken for flax's call shape and ignored."""
        del tokens, state
        return seeded_params(self.param_shapes(), key)

    # -- the layers --------------------------------------------------------

    def _mla(self, p: dict, x: jax.Array, cache, dt):
        """models/mla.py at this net's sizes: a low-rank query, RoPE,
        the scores materialised (512 positions a sequence)."""
        return mla(p, x, cache, dt, self.mla_sizes)

    def _moe(self, p: dict, x: jax.Array, dt, balanced=None):
        """The shared expert layer (models/expert_layer.py) at this
        net's share: x [B, T, hidden] -> (FFN(x), rows routed to each
        held expert [held] int32, the top-k ids [B, T, k])."""
        return expert_ffn(p, x, dt, self.share, balanced)

    def _block(self, p: dict, x: jax.Array, cache, tokens: jax.Array,
               layer: int):
        dt = x.dtype
        g = self.g
        seen = 0 if cache is None else cache[0].shape[1]
        with jax.named_scope("glm.mla"):
            attn, cache = self._mla(
                p, _rms_norm(x, p["input_layernorm"], g.rms_norm_eps),
                cache, dt)
        x = x + attn
        y = _rms_norm(x, p["post_attention_layernorm"], g.rms_norm_eps)
        if "experts" in p["mlp"]:
            with jax.named_scope("glm.moe"):
                balanced = None
                if g.force_balanced_routing:
                    balanced = _balanced_scores(
                        tokens, seen + jnp.arange(x.shape[1]), layer,
                        g.n_routed_experts)
                ffn, rows, ids = self._moe(p["mlp"], y, dt, balanced)
            stats = (rows, ids)
        else:
            with jax.named_scope("glm.dense_ffn"):
                ffn = _swiglu(y, p["mlp"], dt)
            stats = None
        return x + ffn, cache, stats

    # -- entry points ------------------------------------------------------

    def apply_with_stats(self, params: dict, tokens: jax.Array,
                         state: Any = ()):
        """-> (q [B, T, A] float32, state, stats): `stats["expert_rows"]`
        [expert layers, held] int32 rows routed to each held expert,
        `stats["topk"]` [expert layers, B, T, k] the selected ids."""
        dt = dtype_of(self.compute_dtype)
        caches = list(state) if state else [None] * self.g.num_hidden_layers
        tokens = tokens.astype(jnp.int32)
        with jax.named_scope("glm.embed"):
            x = params["embed_tokens"][tokens].astype(dt)
        keep = jax.checkpoint_policies.save_only_these_names(SELECTION)
        new_state, rows, topk = [], [], []
        for layer, (p, cache) in enumerate(zip(params["layers"], caches)):
            x, cache, stats = jax.checkpoint(
                partial(self._block, layer=layer), policy=keep)(
                p, x, cache, tokens)
            new_state.append(cache)
            if stats is not None:
                rows.append(stats[0])
                topk.append(stats[1])
        with jax.named_scope("glm.head"):
            x = _rms_norm(x, params["norm"], self.g.rms_norm_eps)
            q = jnp.dot(x, params["lm_head"].astype(dt),
                        preferred_element_type=jnp.float32)
        b, t = tokens.shape
        k = self.g.num_experts_per_tok
        stats = {
            "expert_rows": (jnp.stack(rows) if rows else jnp.zeros(
                (0, self.experts_held), jnp.int32)),
            "topk": (jnp.stack(topk) if topk
                     else jnp.zeros((0, b, t, k), jnp.int32))}
        return q, tuple(new_state), stats

    def apply(self, params: dict, tokens: jax.Array, state: Any = ()):
        q, state, _ = self.apply_with_stats(params, tokens, state)
        return q, state
