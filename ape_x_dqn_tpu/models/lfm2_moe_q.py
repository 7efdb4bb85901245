"""LFM2-24B-A2B's decoder (model_type lfm2_moe) as a token-level
Q-network of the sequence family, the sixth decoder kind, the first
whose mixer is a convolution and the first whose head is its own
embedding: tokens in, Q(s_t, .) = x_t E^T over the vocabulary rows held
here.

    apply(params, tokens[B, T] int32, state) -> (q[B, T, A] f32, state)

`state` is a tuple over layers of TWO KINDS, `()` for none:
- a conv layer's `(z_tail [B, K - 1, hidden], seen)`: the last K - 1 = 2
  rows of z = B * x~ - POST-GATE, PRE-FILTER, which is where this cache
  differs from every other conv tail here (Kimi's are the projections'
  own rows) - and how many positions came before (int32; only the
  forced balanced selection's hash reads it). Two rows however long the
  prefix was: 8 KiB a sequence and layer in bfloat16;
- an attention layer's `(k, v, seen)`: models/windowed_gqa.py's full
  kind, keys (after their norm and rotation) and values per position.
R2D2's burn-in (ops/losses.make_r2d2_loss, unedited) is a prefix pass
that leaves both; the loss stops their gradient. Nothing is stored with
a sequence.

The equations (benchmarks/reference/lfm2_moe_q.py writes them again in
float32, independently). What the catalog's config.json keys state is
as published; what the `lfm2_moe` family's modelling code (LFM2
technical report, arXiv:2511.23404) adds beyond them is marked (+) and
listed under `assumed` in the benchmark's configuration file. H =
hidden_size.

- RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g, eps `norm_eps`,
  statistics in float32; no bias anywhere (`conv_bias` false).
  x0 = E[token], (+) no scale.
- Block (+ the names): h = x + Op(RMSNorm(x; operator_norm));
  y = h + FFN(RMSNorm(h; ffn_norm)); after the last block one RMSNorm
  ((+) `embedding_norm`), then the head. `layer_types[i]` says which
  Op; the first `num_dense_layers` layers' FFN is one dense SwiGLU,
  every other layer's the expert layer.
- Conv operator, on u = RMSNorm(x): [B | C | x~] = u W_in (H -> 3 H,
  (+) three blocks in that order); z = B * x~; c_t = sum_{j < K} w_j *
  z_{t - (K - 1) + j}, K = `conv_L_cache` = 3, one filter a channel,
  zeros before the first position, (+) NO ACTIVATION (Kimi's and
  Mamba's filters are followed by SiLU: models/short_conv.py is the
  sum alone); Op = (C * c) W_out (H -> H). Neither attention nor a
  scan: two small matmuls around element-wise work over [T, 3 H].
- Attention operator: q, k, v = u W_q, u W_k, u W_v (heads x d, kv
  heads x d; (+) d = H / heads where `head_dim` is null); (+) q and k
  each through an RMSNorm over a head's d dims (`q_layernorm`,
  `k_layernorm`); RoPE on every dim of q and k (theta `rope_theta`, no
  scaling, (+) half-split pairing), positions running on across the
  prefix; causal, full; score q . k / sqrt(d); softmax in float32;
  W_o (`out_proj`). No output gate. models/windowed_gqa.py's call
  (ops/blockwise_attention.py, the one-nest backward schedule at a
  group of 4).
- Expert layer: models/expert_layer.py as it stands (SIGMOID scoring
  with the fixed bias `use_expert_bias`, weights normalised x
  `routed_scaling_factor`, no shared expert). (+) the family divides by
  the sum + 1e-6, the shared module by the sum + 1e-20: with four
  sigmoids the two differ by under 1e-6 relative.
- (+) `tie_embedding`: ONE matrix `embed_tokens` [A, H] with two uses,
  the lookup and the head; counted once, its gradient the sum of the
  lookup's scatter-add and the head's. The loss reads the head by
  column (`head_at`: models/q_head.py's read over [A, H], whose
  gathered rows are E's own and whose scatter-add lands in the
  parameter's layout).
- The share (Lfm2MoeConfig.shard_count / vocab_shard_count /
  shard_index): as Trinity-Mini's; the experts go `shard_count` ways,
  the embedding's rows `vocab_shard_count` ways.

WHAT IS ROUNDED TO THE COMPUTE DTYPE IS ROUNDED BY `ouro_q._held`:
norms' outputs, every projection's output, z (what the state keeps),
the gated C * c, RoPE's output, both residual sums, the dense FFN, the
embedding. The filter's taps and its sum are float32.
models/expert_layer.py rounds by `astype`, as in the nets that share it
(their programs are pinned).

Recomputation: every block is a `jax.checkpoint` that keeps THE
SELECTION (expert_layer.SELECTION) and nothing else.

Scopes: `lfm2.embed`; `lfm2.conv` around a conv operator, inside it
`lfm2.conv.in` (W_in), `lfm2.conv.mix` (both gates and the filter:
nothing that is a matmul), `lfm2.conv.out` (W_out); `afmoe.attn`
around an attention operator (`afmoe.attn.full` inside, opened by
windowed_gqa.attend); `glm.moe` / `lfm2.dense_ffn`; `lfm2.head`.
Counters (`apply_with_stats`): the expert layer's `expert_rows` and
`topk`; `conv_positions`, the positions that passed a conv operator,
summed where the operator runs (conv layers x B x T a pass).

The inference server's protocol is the family's stateless window
(runtime/family.server_apply_fn: a window of one token pads with zeros
on the filter's left); no benchmark cell drives it, and a per-slot
cache there would hold two rows a conv layer. Parameters are float32,
cast to the compute dtype at use; a plain pytree (`embed_tokens`,
`layers`, `embedding_norm`; a conv layer's `in_proj`, `conv_weight`,
`out_proj`, an attention layer's `q_proj`, `k_proj`, `v_proj`,
`out_proj`, `q_layernorm`, `k_layernorm`).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from ape_x_dqn_tpu.models import windowed_gqa
from ape_x_dqn_tpu.models.base import dtype_of
from ape_x_dqn_tpu.models.expert_layer import (
    SELECTION, ExpertShare, _balanced_scores, _rope, count_params,
    expert_ffn, seeded_params)
from ape_x_dqn_tpu.models.ouro_q import _add, _dot, _held, _norm
from ape_x_dqn_tpu.models.q_head import q_at
from ape_x_dqn_tpu.models.short_conv import behind, short_conv
from ape_x_dqn_tpu.ops.blockwise_attention import BLOCK_K, BLOCK_Q

CONV, FULL = "conv", "full_attention"


def _out_gate(c32: jax.Array, mixed32: jax.Array) -> jax.Array:
    """The second gate: C * conv(z), float32."""
    return c32 * mixed32


_head_norm = _norm          # (+) q's and k's, over a head's dims


class Lfm2MoeQNet:
    """The net as a value: `init(key, tokens, state)` and
    `apply(params, tokens, state)`; `c` is a configs.Lfm2MoeConfig."""

    def __init__(self, c: Any, compute_dtype: str = "bfloat16",
                 expert_exchange: bool = False,
                 attn_blocks: tuple[int, int] = (BLOCK_Q, BLOCK_K)):
        """`expert_exchange`, `attn_blocks`: as AfmoeQNet's."""
        if c.conv_bias or not c.use_expert_bias or not c.tie_embedding:
            raise NotImplementedError(
                "network.lfm2_moe: only the published conv_bias=False, "
                "use_expert_bias=True and tie_embedding=True are built")
        if (len(c.layer_types) != c.num_hidden_layers
                or set(c.layer_types) - {CONV, FULL}):
            raise ValueError(
                f"network.lfm2_moe.layer_types names "
                f"{len(c.layer_types)} layers {sorted(set(c.layer_types))} "
                f"but num_hidden_layers={c.num_hidden_layers}: give one "
                f"of {CONV!r} / {FULL!r} for each layer held")
        self.c = c
        self.compute_dtype = compute_dtype
        self.attn_blocks = attn_blocks
        self.head_dim = c.head_dim or c.hidden_size // c.num_attention_heads
        self.num_actions = c.vocab_size // (c.vocab_shard_count
                                            or c.shard_count)
        self.experts_held = c.num_experts // c.shard_count
        self.first_expert = c.shard_index * self.experts_held
        self.num_dense_layers = min(c.num_dense_layers, c.num_hidden_layers)
        self.num_conv_layers = c.layer_types.count(CONV)
        self.router_trains = c.shard_count == 1 or expert_exchange
        self.share = ExpertShare(
            experts=c.num_experts, top_k=c.num_experts_per_tok,
            held=self.experts_held, first=self.first_expert,
            norm_topk=c.norm_topk_prob, scale=c.routed_scaling_factor,
            router_trains=self.router_trains)

    # -- parameters --------------------------------------------------------

    def param_shapes(self) -> dict:
        """The parameter pytree as shapes (matrices are [in, out], a
        layer's held experts stacked on a leading axis; the embedding
        [A, hidden] is the head too)."""
        c, h, d = self.c, self.c.hidden_size, self.head_dim
        q_out, kv_out = c.num_attention_heads * d, c.num_key_value_heads * d

        def ffn(mid, lead=()):
            return {"gate_proj": (*lead, h, mid),
                    "up_proj": (*lead, h, mid),
                    "down_proj": (*lead, mid, h)}

        operators = {
            CONV: {"in_proj": (h, 3 * h), "conv_weight": (c.conv_L_cache, h),
                   "out_proj": (h, h)},
            FULL: {"q_proj": (h, q_out), "k_proj": (h, kv_out),
                   "v_proj": (h, kv_out), "out_proj": (q_out, h),
                   "q_layernorm": (d,), "k_layernorm": (d,)}}
        moe = {"gate": (h, c.num_experts),
               "e_score_correction_bias": (c.num_experts,),
               "experts": ffn(c.moe_intermediate_size, (self.experts_held,))}
        layers = [
            {"operator_norm": (h,), **operators[kind], "ffn_norm": (h,),
             "mlp": (ffn(c.intermediate_size)
                     if index < self.num_dense_layers else moe)}
            for index, kind in enumerate(c.layer_types)]
        return {"embed_tokens": (self.num_actions, h), "layers": layers,
                "embedding_norm": (h,)}

    def param_count(self) -> int:
        return count_params(self.param_shapes())

    def sequence_state_bytes(self, batch_size: int, positions: int) -> int:
        """What a prefix of `positions` leaves, for the HBM fits-check
        (runtime/family.hbm_price): a conv layer two rows whatever
        `positions` is, an attention layer keys and values per
        position, both in the compute dtype."""
        c = self.c
        item = jnp.dtype(dtype_of(self.compute_dtype)).itemsize
        conv = (c.conv_L_cache - 1) * c.hidden_size
        full = 2 * positions * c.num_key_value_heads * self.head_dim
        return batch_size * item * (
            self.num_conv_layers * conv
            + (c.num_hidden_layers - self.num_conv_layers) * full)

    def step_transient_bytes(self, batch_size: int,
                             trained_steps: int) -> int:
        """What a train step holds beside the persistent state (16 B a
        parameter), for the HBM fits-check: AfmoeQNet's two moments (its
        docstring; ONE float32 [tokens, vocabulary held] array, the loss
        reads the head by column), with this net's numbers: 12 B x
        hidden a token beside the expert block's 8, a conv operator's
        [tokens, 3 hidden] in the compute dtype and its gated products.
        Anchor (PR 50, published widths, 5 layers, batch 2 x 12,288
        trained; PERF.md section 4): compiled for a described v5e the
        step's temp is 4.53 GiB, this gives 4.56 (the second moment's)."""
        c = self.c
        tokens = batch_size * trained_steps
        logits = tokens * self.num_actions * 4
        block = tokens * c.hidden_size * (12 * c.num_experts_per_tok + 12)
        return max(logits, 4 * self.param_count() + block)

    def init(self, key: jax.Array, tokens: Any = None,
             state: Any = None) -> dict:
        """Seeded float32 parameters (expert_layer.seeded_params: every
        matrix and filter normal(0, 0.02), gains 1). `tokens`/`state`
        are taken for flax's call shape and ignored."""
        del tokens, state
        return seeded_params(self.param_shapes(), key)

    def _head_rows(self, params: dict) -> jax.Array:
        """The head's matrix [A, hidden]: the embedding itself."""
        return params["embed_tokens"]

    def head_at(self, params: dict, x: jax.Array,
                ids: jax.Array) -> jax.Array:
        """The head's input x [B, T, hidden] (`stats["head_input"]`),
        ids [B, T] -> Q(s_t, ids_t) = x_t . E[ids_t], [B, T] float32."""
        return q_at(x, self._head_rows(params), ids, by_row=True)

    # -- the layers --------------------------------------------------------

    def _conv(self, p: dict, u: jax.Array, tail):
        """u = RMSNorm(x) [B, T, hidden] -> (the operator's output [B, T,
        hidden], the last K - 1 rows of z, positions that passed)."""
        dt, f32 = u.dtype, jnp.float32
        b, t, h = u.shape
        with jax.named_scope("lfm2.conv.in"):
            bcx = _dot(u, p["in_proj"])
        with jax.named_scope("lfm2.conv.mix"):
            gate_b, gate_c, x = (bcx[..., i * h:(i + 1) * h].astype(f32)
                                 for i in range(3))
            z = _held(gate_b * x, dt)
            seen = behind(tail, z, self.c.conv_L_cache)
            y = _held(_out_gate(
                gate_c, short_conv(seen, p["conv_weight"], t)), dt)
        with jax.named_scope("lfm2.conv.out"):
            out = _dot(y, p["out_proj"])
        return out, seen[:, t:], jnp.int32(b * t)

    def _attention(self, p: dict, u: jax.Array, cache, positions):
        """u = RMSNorm(x) [B, T, hidden] -> (attention output [B, T,
        hidden], this layer's (k, v) with the new positions)."""
        c, dt, d = self.c, u.dtype, self.head_dim
        b, t, _ = u.shape

        def heads(w, n):
            return _dot(u, w).reshape(b, t, n, d)

        def rotated(x):
            return _held(_rope(x.astype(jnp.float32), positions,
                               c.rope_theta), dt)

        q = rotated(_head_norm(heads(p["q_proj"], c.num_attention_heads),
                               p["q_layernorm"], c.norm_eps))
        k = rotated(_head_norm(heads(p["k_proj"], c.num_key_value_heads),
                               p["k_layernorm"], c.norm_eps))
        v = heads(p["v_proj"], c.num_key_value_heads)
        out = windowed_gqa.attend(q, k, v, cache, None, self.attn_blocks)
        kv = windowed_gqa.extend(cache, k, v, None)
        return _dot(out.reshape(b, t, -1), p["out_proj"]), kv

    @staticmethod
    def _dense_ffn(p: dict, y: jax.Array) -> jax.Array:
        f32 = jnp.float32
        gate, up = _dot(y, p["gate_proj"]), _dot(y, p["up_proj"])
        mid = _held(jax.nn.silu(gate.astype(f32)) * up.astype(f32), y.dtype)
        return _dot(mid, p["down_proj"])

    def _block(self, p: dict, x: jax.Array, cache, tokens: jax.Array,
               layer: int):
        c, dt, eps = self.c, x.dtype, self.c.norm_eps
        seen = jnp.int32(0) if cache is None else cache[-1]
        positions = seen + jnp.arange(x.shape[1], dtype=jnp.int32)
        u = _norm(x, p["operator_norm"], eps)
        passed = jnp.int32(0)
        if c.layer_types[layer] == CONV:
            with jax.named_scope("lfm2.conv"):
                mixed, tail, passed = self._conv(
                    p, u, None if cache is None else cache[0])
            kept = (tail,)
        else:
            with jax.named_scope("afmoe.attn"):
                mixed, kept = self._attention(
                    p, u, None if cache is None else cache[:2], positions)
        x = _add(x, mixed)
        y = _norm(x, p["ffn_norm"], eps)
        if "experts" in p["mlp"]:
            with jax.named_scope("glm.moe"):
                balanced = None
                if c.force_balanced_routing:
                    balanced = _balanced_scores(tokens, positions, layer,
                                                c.num_experts)
                ffn, rows, ids = expert_ffn(p["mlp"], y, dt, self.share,
                                            balanced)
            stats = (rows, ids)
        else:
            with jax.named_scope("lfm2.dense_ffn"):
                ffn = self._dense_ffn(p["mlp"], y)
            stats = None
        return _add(x, ffn), (*kept, seen + x.shape[1]), stats, passed

    # -- entry points ------------------------------------------------------

    def apply_with_stats(self, params: dict, tokens: jax.Array,
                         state: Any = ()):
        """-> (q [B, T, A] float32, state, stats): `expert_rows` [expert
        layers, held] int32, `topk` [expert layers, B, T, k],
        `head_input` [B, T, hidden] what the head read (the loss's
        column read goes over it: `head_at`), `conv_positions` int32
        (the module docstring)."""
        c = self.c
        if tokens.shape[1] > c.max_position_embeddings:
            raise ValueError(
                f"{tokens.shape[1]} tokens in one pass, but "
                f"network.lfm2_moe.max_position_embeddings="
                f"{c.max_position_embeddings}")
        dt = dtype_of(self.compute_dtype)
        caches = list(state) if state else [None] * c.num_hidden_layers
        tokens = tokens.astype(jnp.int32)
        with jax.named_scope("lfm2.embed"):
            x = _held(params["embed_tokens"][tokens], dt)
        keep = jax.checkpoint_policies.save_only_these_names(SELECTION)
        new_state, rows, topk = [], [], []
        passed = jnp.int32(0)
        for layer, (p, cache) in enumerate(zip(params["layers"], caches)):
            x, cache, stats, passed_l = jax.checkpoint(
                partial(self._block, layer=layer), policy=keep)(
                p, x, cache, tokens)
            new_state.append(cache)
            passed = passed + passed_l
            if stats is not None:
                rows.append(stats[0])
                topk.append(stats[1])
        with jax.named_scope("lfm2.head"):
            x = _norm(x, params["embedding_norm"], c.norm_eps)
            q = jnp.einsum("bth,ah->bta", x,
                           self._head_rows(params).astype(dt),
                           preferred_element_type=jnp.float32)
        b, t = tokens.shape
        stats = {
            "head_input": x,
            "expert_rows": (jnp.stack(rows) if rows else jnp.zeros(
                (0, self.experts_held), jnp.int32)),
            "topk": (jnp.stack(topk) if topk else jnp.zeros(
                (0, b, t, c.num_experts_per_tok), jnp.int32)),
            "conv_positions": passed}
        return q, tuple(new_state), stats

    def apply(self, params: dict, tokens: jax.Array, state: Any = ()):
        q, state, _ = self.apply_with_stats(params, tokens, state)
        return q, state
