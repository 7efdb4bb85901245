"""Trinity-Mini's decoder (model_type afmoe) as a token-level Q-network
of the sequence family, the second decoder kind beside
models/glm_moe_q.py: tokens in, Q(s_t, .) = the model's own untied head
over the vocabulary rows held here.

    apply(params, tokens[B, T] int32, state) -> (q[B, T, A] f32, state)

`state` is the cache of the positions already seen, TWO KINDS SIDE BY
SIDE: per layer `(k [B, C, kv heads, d], v, seen)`, where a
"full_attention" layer keeps every position (C = seen) and a
"sliding_attention" layer its last `sliding_window - 1` (what the next
query's window can still reach; a longer cache changes nothing), and
`seen` (int32 scalar) is how many positions came before, which a
trimmed cache no longer says. `()` is none. The new tokens take
positions seen .. seen + T - 1. That makes R2D2's burn-in
(ops/losses.make_r2d2_loss, unedited) a prefix pass: it leaves the
cache, the loss stops its gradient, the trained segment attends to it.
Nothing is stored with a sequence.

The equations (benchmarks/reference/afmoe_q.py writes them again in
float32, independently). What the catalog's config.json keys state is
as published; what the afmoe modelling code adds beyond them is marked
(+) and listed under `assumed` in the benchmark's configuration file.

- RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g, statistics in
  float32. No biases anywhere.
- Embedding: x0 = E[token] * sqrt(hidden) (`mup_enabled`; (+) that muP
  here means exactly this scale).
- Block (+ four norms): h = x + N2(Attn(N1(x))); y = h + N4(FFN(N3(h)))
  (`input_layernorm`, `post_attention_layernorm`, `pre_mlp_layernorm`,
  `post_mlp_layernorm`). After the last block RMSNorm, then the head.
- Attention, u = N1(x): q = u W_q -> heads x d; k = u W_k, v = u W_v ->
  kv heads x d; (+) q and k each through an RMSNorm over a head's d
  dims (`q_norm`, `k_norm`: one gain vector each). A
  "sliding_attention" layer rotates q and k (RoPE: theta, every dim,
  half-split pairing, no scaling; positions run on across the prefix
  boundary); (+) a "full_attention" layer has no position encoding.
  score = q . k / sqrt(d), query head j reads key-value head j // (heads
  / kv heads); key s is visible to query t iff s <= t and, on sliding
  layers, t - s < sliding_window; softmax in float32; o = sum p v.
  (+) Output gate: o <- o * sigmoid(u W_gate), W_gate hidden -> heads x
  d. Then W_o. The cache holds k after its norm and rotation.
  The call and the cache of two kinds are models/windowed_gqa.py's
  (shared with models/smallthinker_q.py; head norms and the gate are
  this net's own, around it); ops/blockwise_attention.py computes both
  kinds with their mask, never forming a [T, S] array and skipping the
  key blocks the mask rules out.
- FFN: the first `num_dense_layers` layers one SwiGLU of
  `intermediate_size`; the rest models/expert_layer.py's routed +
  shared expert layer (its docstring has the equations, the share, the
  forced balanced selection and how the matmuls run) at this model's
  numbers. Its parameters keep that module's names (`gate`,
  `e_score_correction_bias`, `experts`, `shared_experts`; afmoe's own
  are `router.gate` and `expert_bias`).
- The share (AfmoeConfig.shard_count / shard_index): as GLM's.
  Embedding and head hold vocab_size / vocab_shard_count rows (the
  vocabulary may go fewer ways than the experts).
- `load_balance_coeff` is the pre-training rate of b's update, which a
  TD loss does not run: b is a seeded, fixed buffer.

Recomputation: every block is a `jax.checkpoint` that keeps THE
SELECTION (expert_layer.SELECTION) and nothing else.

The inference server's protocol is the family's stateless window
(runtime/family.server_apply_fn): a query carries the last <= L token
ids and the server re-runs them from an empty cache. At L = 8,192 that
is a whole 8,192-token forward (6 TFLOP at the benchmark's 1 + 4
layers) per action; a per-slot cache of these two kinds inside
parallel/inference_server.py is what would make it one token
(ROADMAP R2.1). No benchmark cell drives it.

Parameters are float32, cast to the compute dtype at use; a plain
pytree (`embed_tokens`, `layers`, `norm`, `lm_head`), as GlmMoeQNet's.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from ape_x_dqn_tpu.models.base import dtype_of
from ape_x_dqn_tpu.models.expert_layer import (
    SELECTION, ExpertShare, _balanced_scores, _rms_norm, _rope, _swiglu,
    count_params, expert_ffn, seeded_params)
from ape_x_dqn_tpu.models import windowed_gqa
from ape_x_dqn_tpu.models.q_head import ColumnHead
from ape_x_dqn_tpu.ops.blockwise_attention import BLOCK_K, BLOCK_Q

SLIDING = "sliding_attention"


class AfmoeQNet(ColumnHead):
    """The net as a value: `init(key, tokens, state)` and
    `apply(params, tokens, state)`; `a` is a configs.AfmoeConfig."""

    def __init__(self, a: Any, compute_dtype: str = "bfloat16",
                 expert_exchange: bool = False,
                 attn_blocks: tuple[int, int] = (BLOCK_Q, BLOCK_K)):
        """`expert_exchange`: as GlmMoeQNet's. `attn_blocks`: the
        attention's (query, key) block sizes; tests shrink them so that
        tiny sequences still cross a block boundary."""
        if a.n_group != 1 or a.topk_group != 1:
            raise NotImplementedError(
                "network.afmoe: only n_group = topk_group = 1 is built "
                "(no group stage in the expert selection)")
        if len(a.layer_types) != a.num_hidden_layers:
            raise ValueError(
                f"network.afmoe.layer_types names {len(a.layer_types)} "
                f"layers but num_hidden_layers={a.num_hidden_layers}: give "
                f"one kind for each layer held")
        self.a = a
        self.compute_dtype = compute_dtype
        self.attn_blocks = attn_blocks
        self.num_actions = a.vocab_size // (a.vocab_shard_count
                                            or a.shard_count)
        self.experts_held = a.num_experts // a.shard_count
        self.first_expert = a.shard_index * self.experts_held
        self.num_dense_layers = min(a.num_dense_layers, a.num_hidden_layers)
        self.router_trains = a.shard_count == 1 or expert_exchange
        self.share = ExpertShare(
            experts=a.num_experts, top_k=a.num_experts_per_tok,
            held=self.experts_held, first=self.first_expert,
            norm_topk=a.route_norm, scale=a.route_scale,
            router_trains=self.router_trains)

    # -- parameters --------------------------------------------------------

    def param_shapes(self) -> dict:
        """The parameter pytree as shapes (matrices are [in, out], a
        layer's held experts stacked on a leading axis)."""
        a, h = self.a, self.a.hidden_size
        q_out = a.num_attention_heads * a.head_dim
        kv_out = a.num_key_value_heads * a.head_dim

        def ffn(width, lead=()):
            return {"gate_proj": (*lead, h, width),
                    "up_proj": (*lead, h, width),
                    "down_proj": (*lead, width, h)}

        def attention():
            return {
                "input_layernorm": (h,),
                "q_proj": (h, q_out), "k_proj": (h, kv_out),
                "v_proj": (h, kv_out), "gate_proj": (h, q_out),
                "o_proj": (q_out, h),
                "q_norm": (a.head_dim,), "k_norm": (a.head_dim,),
                "post_attention_layernorm": (h,),
                "pre_mlp_layernorm": (h,), "post_mlp_layernorm": (h,),
            }

        moe = {
            "gate": (h, a.num_experts),
            "e_score_correction_bias": (a.num_experts,),
            "experts": ffn(a.moe_intermediate_size, (self.experts_held,)),
            "shared_experts": ffn(
                a.moe_intermediate_size * a.num_shared_experts)}
        layers = [
            {**attention(), "mlp": (ffn(a.intermediate_size)
                                    if i < self.num_dense_layers else moe)}
            for i in range(a.num_hidden_layers)]
        return {"embed_tokens": (self.num_actions, h), "layers": layers,
                "norm": (h,), "lm_head": (h, self.num_actions)}

    def param_count(self) -> int:
        return count_params(self.param_shapes())

    def step_transient_bytes(self, batch_size: int,
                             trained_steps: int) -> int:
        """What a train step holds beside the persistent state (16 B a
        parameter), for the HBM fits-check, as GlmMoeQNet's. Two moments
        compete for the peak: the end of the forward pass, when the
        online net's float32 Q-values [batch, trained steps, vocabulary
        held] are alive (ONE such array: the loss reads the target net
        and Q(s, a) by column, models/q_head.py; two before PR 49); and
        the backward pass of the first expert block, when nearly all
        gradients exist (4 B a parameter) beside one block's recomputed
        activations and its dispatch buffers, which run over all k assignment rows of a
        token in bfloat16 and float32 (12 B x hidden a row, 8 B x
        hidden a token beside them: fitted to the one reading there
        is). Anchor (PR 32, published widths, 1 + 4 layers, batch 2 x
        6,144 trained; PERF.md section 4): compiled for a described
        v5e the step's temp is 4.28 GiB, this gives 4.28 (the second
        moment's; PR 49, the same shapes with the head by column: temp
        4.29 GiB before and after); the inference
        server's own float32 copy of the parameters (1.88 GiB), which
        nothing prices, comes on top."""
        tokens = batch_size * trained_steps
        logits = tokens * self.num_actions * 4
        block = tokens * self.a.hidden_size * (
            12 * self.a.num_experts_per_tok + 8)
        return max(logits, 4 * self.param_count() + block)

    def init(self, key: jax.Array, tokens: Any = None,
             state: Any = None) -> dict:
        """Seeded float32 parameters (expert_layer.seeded_params).
        `tokens`/`state` are taken for flax's call shape and ignored."""
        del tokens, state
        return seeded_params(self.param_shapes(), key)

    # -- the layers --------------------------------------------------------

    def _attention(self, p: dict, u: jax.Array, cache, positions, kind: str):
        """u = N1(x) [B, T, hidden] -> (attention output [B, T, hidden],
        this layer's (k, v) cache with the new positions)."""
        a, dt = self.a, u.dtype
        b, t, _ = u.shape
        sliding = kind == SLIDING
        q = (u @ p["q_proj"].astype(dt)).reshape(
            b, t, a.num_attention_heads, a.head_dim)
        k = (u @ p["k_proj"].astype(dt)).reshape(
            b, t, a.num_key_value_heads, a.head_dim)
        v = (u @ p["v_proj"].astype(dt)).reshape(
            b, t, a.num_key_value_heads, a.head_dim)
        q = _rms_norm(q, p["q_norm"], a.rms_norm_eps)
        k = _rms_norm(k, p["k_norm"], a.rms_norm_eps)
        if sliding:
            q = _rope(q, positions, a.rope_theta)
            k = _rope(k, positions, a.rope_theta)
        window = a.sliding_window if sliding else None
        out = windowed_gqa.attend(q, k, v, cache, window, self.attn_blocks)
        gate = jax.nn.sigmoid(u @ p["gate_proj"].astype(dt))
        out = out.reshape(b, t, -1) * gate
        kv = windowed_gqa.extend(cache, k, v, window)
        return out @ p["o_proj"].astype(dt), kv

    def _block(self, p: dict, x: jax.Array, cache, tokens: jax.Array,
               layer: int):
        a, eps = self.a, self.a.rms_norm_eps
        dt = x.dtype
        seen, positions = windowed_gqa.positions_after(cache, x.shape[1])
        with jax.named_scope("afmoe.attn"):
            attn, kv = self._attention(
                p, _rms_norm(x, p["input_layernorm"], eps),
                None if cache is None else cache[:2], positions,
                a.layer_types[layer])
            x = x + _rms_norm(attn, p["post_attention_layernorm"], eps)
        y = _rms_norm(x, p["pre_mlp_layernorm"], eps)
        if "experts" in p["mlp"]:
            with jax.named_scope("glm.moe"):
                balanced = None
                if a.force_balanced_routing:
                    balanced = _balanced_scores(tokens, positions, layer,
                                                a.num_experts)
                ffn, rows, ids = expert_ffn(p["mlp"], y, dt, self.share,
                                            balanced)
            stats = (rows, ids)
        else:
            with jax.named_scope("afmoe.dense_ffn"):
                ffn = _swiglu(y, p["mlp"], dt)
            stats = None
        x = x + _rms_norm(ffn, p["post_mlp_layernorm"], eps)
        return x, (*kv, seen + x.shape[1]), stats

    # -- entry points ------------------------------------------------------

    def apply_with_stats(self, params: dict, tokens: jax.Array,
                         state: Any = ()):
        """-> (q [B, T, A] float32, state, stats): `stats["expert_rows"]`
        [expert layers, held] int32 rows routed to each held expert,
        `stats["topk"]` [expert layers, B, T, k] the selected ids,
        `stats["head_input"]` [B, T, hidden] what the head read (the
        loss's column read goes over it: `head_at`)."""
        a = self.a
        dt = dtype_of(self.compute_dtype)
        caches = list(state) if state else [None] * a.num_hidden_layers
        tokens = tokens.astype(jnp.int32)
        with jax.named_scope("afmoe.embed"):
            x = params["embed_tokens"][tokens].astype(dt)
            if a.mup_enabled:
                x = x * jnp.asarray(math.sqrt(a.hidden_size), dt)
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
        with jax.named_scope("afmoe.head"):
            x = _rms_norm(x, params["norm"], a.rms_norm_eps)
            q = jnp.dot(x, params["lm_head"].astype(dt),
                        preferred_element_type=jnp.float32)
        b, t = tokens.shape
        stats = {
            "head_input": x,
            "expert_rows": (jnp.stack(rows) if rows else jnp.zeros(
                (0, self.experts_held), jnp.int32)),
            "topk": (jnp.stack(topk) if topk else jnp.zeros(
                (0, b, t, a.num_experts_per_tok), jnp.int32))}
        return q, tuple(new_state), stats

    def apply(self, params: dict, tokens: jax.Array, state: Any = ()):
        q, state, _ = self.apply_with_stats(params, tokens, state)
        return q, state
