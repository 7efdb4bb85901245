"""SmallThinker-21BA3B-Instruct's decoder as a token-level Q-network of
the sequence family, the third decoder kind beside models/glm_moe_q.py
and models/afmoe_q.py: tokens in, Q(s_t, .) = the model's own untied
head over the vocabulary rows held here.

    apply(params, tokens[B, T] int32, state) -> (q[B, T, A] f32, state)

`state` is models/windowed_gqa.py's cache of two kinds, per layer
`(k, v, seen)`: every position on a global layer, the last
`sliding_window_size - 1` on a sliding one. `()` is none. R2D2's
burn-in (ops/losses.make_r2d2_loss, unedited) is a prefix pass that
leaves it; the loss stops its gradient. Nothing is stored with a
sequence.

The equations (benchmarks/reference/smallthinker_q.py writes them again
in float32, independently). What the catalog's config.json keys state
is as published; what the family's modelling code or the catalog's
description adds beyond them is marked (+) and listed under `assumed`
in the benchmark's configuration file.

- RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g, statistics in
  float32; (+) no bias anywhere. Embedding: x0 = E[token], no scale.
- Block l, input x, u = N1(x) (`input_layernorm`):
      (+) r = u W_router            (hidden -> all experts, float32)
      h = x + Attn(u)
      y = h + MoE(N2(h); r)         (`post_attention_layernorm`)
  Two norms a block; after the last block RMSNorm, then the head.
  THE ROUTER READS AHEAD OF ATTENTION: the catalog row says "router
  placed before attention" and no key says which tensor; that it is
  the attention's NORMED input N1(x) is (+). Either way r does not come
  from N2(h), the rows the experts are fed, so the layer's plan
  (expert_layer.plan: selection, weights, sort, counts) is made before
  attention starts and waits for nothing attention computes.
- Attention: q = u W_q -> heads x d; k, v -> kv heads x d; query head j
  reads key-value head j // (heads / kv heads) (28 / 4 = 7); no head
  norms, no output gate. `sliding_window_layout[l]` = 1: key s is
  visible to query t iff 0 <= t - s < `sliding_window_size` ((+) the
  window counts the query); `rope_layout[l]` = 1: RoPE on q and k
  (theta, every dim, half-split pairing, no scaling; positions run on
  across the prefix). Layout 0: every earlier key, no position
  encoding. score = q . k / sqrt(d), softmax in float32.
- MoE: ids = top-`moe_num_active_primary_experts` of r; weights =
  SOFTMAX OVER THE SELECTED LOGITS (`moe_primary_router_apply_softmax`
  with `norm_topk_prob`: a softmax over all experts renormalised over
  the selected is the softmax over the selected); expert e:
  (relu(z W_gate_e) * (z W_up_e)) W_down_e ((+) "sparse ReGLU");
  MoE = sum over the selected of w_e expert_e(z). No shared expert, no
  dense layer, (+) no secondary experts. models/expert_layer.py runs it
  (its docstring has the share, the forced balanced selection and how
  the matmuls run); the router's matrix keeps that module's name
  (`gate`; the model's own is `primary_router`).
- The share (SmallThinkerConfig.shard_count / shard_index): as GLM's;
  embedding and head hold vocab_size / shard_count rows.

Recomputation: every block is a `jax.checkpoint` that keeps THE
SELECTION (expert_layer.SELECTION) and nothing else.

Scopes: `st.embed`, `st.route_ahead` (the router's matmul on N1(x) and
the whole plan; expert_layer's `glm.moe.router` / `glm.moe.dispatch`
nest inside it), `afmoe.attn` (projections, RoPE, the shared attention
call, output projection), `glm.moe` (gather, grouped matmuls, combine),
`st.head`.

The inference server's protocol is the family's stateless window
(runtime/family.server_apply_fn); no benchmark cell drives it.
Parameters are float32, cast to the compute dtype at use; a plain
pytree (`embed_tokens`, `layers`, `norm`, `lm_head`).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from ape_x_dqn_tpu.models import windowed_gqa
from ape_x_dqn_tpu.models.base import dtype_of
from ape_x_dqn_tpu.models.expert_layer import (
    SELECTION, SOFTMAX_SELECTED, ExpertShare, _balanced_scores, _rms_norm,
    _rope, count_params, expert_ffn, plan, seeded_params)
from ape_x_dqn_tpu.models.q_head import ColumnHead
from ape_x_dqn_tpu.ops.blockwise_attention import BLOCK_K, BLOCK_Q


class SmallThinkerQNet(ColumnHead):
    """The net as a value: `init(key, tokens, state)` and
    `apply(params, tokens, state)`; `s` is a configs.SmallThinkerConfig."""

    def __init__(self, s: Any, compute_dtype: str = "bfloat16",
                 expert_exchange: bool = False,
                 attn_blocks: tuple[int, int] = (BLOCK_Q, BLOCK_K)):
        """`expert_exchange`, `attn_blocks`: as AfmoeQNet's."""
        if not (s.moe_primary_router_apply_softmax and s.norm_topk_prob):
            raise NotImplementedError(
                "network.smallthinker: only the published scoring is built "
                "(moe_primary_router_apply_softmax and norm_topk_prob: the "
                "softmax over the selected logits)")
        layouts = (s.rope_layout, s.sliding_window_layout)
        if any(len(x) != s.num_hidden_layers for x in layouts):
            raise ValueError(
                f"network.smallthinker.rope_layout / sliding_window_layout "
                f"name {len(layouts[0])} / {len(layouts[1])} layers but "
                f"num_hidden_layers={s.num_hidden_layers}: give one entry "
                f"for each layer held")
        self.s = s
        self.compute_dtype = compute_dtype
        self.attn_blocks = attn_blocks
        self.num_actions = s.vocab_size // s.shard_count
        self.experts_held = s.moe_num_primary_experts // s.shard_count
        self.first_expert = s.shard_index * self.experts_held
        self.router_trains = s.shard_count == 1 or expert_exchange
        self.share = ExpertShare(
            experts=s.moe_num_primary_experts,
            top_k=s.moe_num_active_primary_experts,
            held=self.experts_held, first=self.first_expert,
            norm_topk=True, scale=1.0, router_trains=self.router_trains)

    # -- parameters --------------------------------------------------------

    def param_shapes(self) -> dict:
        """The parameter pytree as shapes (matrices are [in, out], a
        layer's held experts stacked on a leading axis)."""
        s, h = self.s, self.s.hidden_size
        q_out = s.num_attention_heads * s.head_dim
        kv_out = s.num_key_value_heads * s.head_dim
        held, width = self.experts_held, s.moe_ffn_hidden_size
        layer = {
            "input_layernorm": (h,),
            "q_proj": (h, q_out), "k_proj": (h, kv_out),
            "v_proj": (h, kv_out), "o_proj": (q_out, h),
            "post_attention_layernorm": (h,),
            "mlp": {"gate": (h, s.moe_num_primary_experts),
                    "experts": {"gate_proj": (held, h, width),
                                "up_proj": (held, h, width),
                                "down_proj": (held, width, h)}}}
        return {"embed_tokens": (self.num_actions, h),
                "layers": [layer] * s.num_hidden_layers,
                "norm": (h,), "lm_head": (h, self.num_actions)}

    def param_count(self) -> int:
        return count_params(self.param_shapes())

    def step_transient_bytes(self, batch_size: int,
                             trained_steps: int) -> int:
        """What a train step holds beside the persistent state (16 B a
        parameter), for the HBM fits-check: AfmoeQNet's two moments (its
        docstring; ONE float32 [tokens, vocabulary held] array since the
        loss reads the head by column, PR 49), with this net's numbers.
        Anchor (PR 39, published widths, 4 layers, batch 1 x 12,288
        trained; PERF.md section 4): compiled for a described v5e the
        step's temp is 3.29 GiB, this gives 3.72 (PR 49, the head by
        column: 3.32 GiB compiled, the second moment's)."""
        tokens = batch_size * trained_steps
        logits = tokens * self.num_actions * 4
        block = tokens * self.s.hidden_size * (
            12 * self.s.moe_num_active_primary_experts + 8)
        return max(logits, 4 * self.param_count() + block)

    def init(self, key: jax.Array, tokens: Any = None,
             state: Any = None) -> dict:
        """Seeded float32 parameters (expert_layer.seeded_params).
        `tokens`/`state` are taken for flax's call shape and ignored."""
        del tokens, state
        return seeded_params(self.param_shapes(), key)

    # -- the layers --------------------------------------------------------

    def _attention(self, p: dict, u: jax.Array, cache, positions,
                   layer: int):
        """u = N1(x) [B, T, hidden] -> (attention output [B, T, hidden],
        this layer's (k, v) cache with the new positions)."""
        s, dt = self.s, u.dtype
        b, t, _ = u.shape
        q = (u @ p["q_proj"].astype(dt)).reshape(
            b, t, s.num_attention_heads, s.head_dim)
        k = (u @ p["k_proj"].astype(dt)).reshape(
            b, t, s.num_key_value_heads, s.head_dim)
        v = (u @ p["v_proj"].astype(dt)).reshape(
            b, t, s.num_key_value_heads, s.head_dim)
        if s.rope_layout[layer]:
            q = _rope(q, positions, s.rope_theta)
            k = _rope(k, positions, s.rope_theta)
        window = (s.sliding_window_size if s.sliding_window_layout[layer]
                  else None)
        # no q/k norms and no embedding scale: the rows share one large
        # vector, which ops/blockwise_attention.py takes out (`about_mean`)
        out = windowed_gqa.attend(q, k, v, cache, window, self.attn_blocks,
                                  about_mean=True)
        kv = windowed_gqa.extend(cache, k, v, window)
        return out.reshape(b, t, -1) @ p["o_proj"].astype(dt), kv

    def _block(self, p: dict, x: jax.Array, cache, tokens: jax.Array,
               layer: int):
        s, eps = self.s, self.s.rms_norm_eps
        dt = x.dtype
        seen, positions = windowed_gqa.positions_after(cache, x.shape[1])
        u = _rms_norm(x, p["input_layernorm"], eps)
        with jax.named_scope("st.route_ahead"):
            balanced = None
            if s.force_balanced_routing:
                balanced = _balanced_scores(
                    tokens, positions, layer, s.moe_num_primary_experts
                ).reshape(tokens.size, -1)
            planned = plan(p["mlp"], u.reshape(tokens.size, -1), self.share,
                           balanced, SOFTMAX_SELECTED)
        with jax.named_scope("afmoe.attn"):
            attn, kv = self._attention(
                p, u, None if cache is None else cache[:2], positions, layer)
            x = x + attn
        y = _rms_norm(x, p["post_attention_layernorm"], eps)
        with jax.named_scope("glm.moe"):
            ffn, rows, ids = expert_ffn(p["mlp"], y, dt, self.share,
                                        planned=planned, act=jax.nn.relu)
        return x + ffn, (*kv, seen + x.shape[1]), (rows, ids)

    # -- entry points ------------------------------------------------------

    def apply_with_stats(self, params: dict, tokens: jax.Array,
                         state: Any = ()):
        """-> (q [B, T, A] float32, state, stats): `stats["expert_rows"]`
        [layers, held] int32 rows routed to each held expert,
        `stats["topk"]` [layers, B, T, k] the selected ids,
        `stats["head_input"]` [B, T, hidden] what the head read (the
        loss's column read goes over it: `head_at`)."""
        s = self.s
        if tokens.shape[1] > s.max_position_embeddings:
            raise ValueError(
                f"{tokens.shape[1]} tokens in one pass, but "
                f"network.smallthinker.max_position_embeddings="
                f"{s.max_position_embeddings}")
        dt = dtype_of(self.compute_dtype)
        caches = list(state) if state else [None] * s.num_hidden_layers
        tokens = tokens.astype(jnp.int32)
        with jax.named_scope("st.embed"):
            x = params["embed_tokens"][tokens].astype(dt)
        keep = jax.checkpoint_policies.save_only_these_names(SELECTION)
        new_state, rows, topk = [], [], []
        for layer, (p, cache) in enumerate(zip(params["layers"], caches)):
            x, cache, (rows_l, ids_l) = jax.checkpoint(
                partial(self._block, layer=layer), policy=keep)(
                p, x, cache, tokens)
            new_state.append(cache)
            rows.append(rows_l)
            topk.append(ids_l)
        with jax.named_scope("st.head"):
            x = _rms_norm(x, params["norm"], s.rms_norm_eps)
            q = jnp.dot(x, params["lm_head"].astype(dt),
                        preferred_element_type=jnp.float32)
        return q, tuple(new_state), {"expert_rows": jnp.stack(rows),
                                     "topk": jnp.stack(topk),
                                     "head_input": x}

    def apply(self, params: dict, tokens: jax.Array, state: Any = ()):
        q, state, _ = self.apply_with_stats(params, tokens, state)
        return q, state
