"""Kimi-Linear-48B-A3B-Instruct's decoder (model_type kimi_linear) as a
token-level Q-network of the sequence family, the fifth decoder kind
and the first with a scan layer that is not an LSTM: tokens in,
Q(s_t, .) = the model's own untied head over the vocabulary rows held
here.

    apply(params, tokens[B, T] int32, state) -> (q[B, T, A] f32, state)

`state` is a tuple over layers of TWO KINDS THAT DIFFER IN NATURE, `()`
for none:
- a KDA layer's `(S [B, heads, d, d] float32, conv_tail [B, 3, K - 1,
  heads x d], seen)`: the delta rule's state matrix after the last
  position, the last K - 1 PRE-convolution rows of the q, k and v
  streams, and how many positions came before (int32; only the forced
  balanced selection's hash reads it). The same size however long the
  prefix was;
- an MLA layer's `(c_kv [B, S, kv_lora_rank] after its norm, k_r [B, S,
  qk_rope_head_dim])`: a latent row per prefix position.
R2D2's burn-in (ops/losses.make_r2d2_loss, unedited) is a prefix pass
that leaves both; the loss stops their gradient. Nothing is stored with
a sequence.

The equations (benchmarks/reference/kimi_linear_q.py writes them again
in float32, independently, the KDA one position at a time). What the
catalog's config.json keys state is as published; what the Kimi Linear
report (arXiv:2510.26692) or the family's modelling code adds beyond
them is marked (+) and listed under `assumed` in the benchmark's
configuration file. H = hidden_size.

- RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g, eps 1e-5, statistics
  in float32; no bias anywhere. x0 = E[token].
- Block: h = x + Mixer(RMSNorm(x)); y = h + FFN(RMSNorm(h)); after the
  last block RMSNorm, then the head. Layer l (from 1) is MLA if l is in
  `full_attn_layers`, else KDA; the first `first_k_dense_replace`
  layers' FFN is one dense SwiGLU, every other layer's the expert layer.
- KDA, on u = RMSNorm(x) (heads x d = 32 x 128 = 4096):
      q~, k~, v~ = u W_q, u W_k, u W_v, each through a causal depthwise
      convolution of kernel K = 4 over time (one filter a channel, no
      bias; out_t = sum_j w_j x_{t - (K - 1) + j}: models/short_conv.py,
      which models/lfm2_moe_q.py calls too) and SiLU;
      (+) q = L2norm_head(q~) d^-1/2, k = L2norm_head(k~) (x rsqrt(sum
      x^2 + 1e-6)), v = v~;
      g_t = -exp(A_log_h) softplus(u_t W_f_down W_f_up + dt_bias), per
      head AND per key channel ((+) the low rank is d); alpha = exp(g);
      beta_t = sigmoid(u_t W_beta), one number a head;
      S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T,
      o_t = S_t^T q_t                  (ops/chunked_delta_rule.py);
      y_t = [RMSNorm_head(o_t; o_norm) * sigmoid(u_t W_g_down W_g_up)] W_o
      ((+) gate low rank d; a sigmoid, not SiLU).
  One alpha a head would be Gated DeltaNet; the decay per channel is
  what makes it KDA.
- MLA (models/mla.py: no low-rank query, NO ROTATION - `mla_use_nope`,
  the 64 "rope" dims are plain dims and the KDA layers carry order -,
  the scores through ops/blockwise_attention.py at keys of 192 and
  values of 128, a group of one).
- Expert layer: models/expert_layer.py as GLM calls it (SIGMOID scoring,
  fixed bias, weights normalised x `routed_scaling_factor`, one shared
  expert; `num_expert_group` 1, so no group stage).
- The share (KimiLinearConfig.shard_count / vocab_shard_count /
  shard_index): as Trinity-Mini's; the experts go `shard_count` ways,
  embedding and head rows `vocab_shard_count` ways.

WHAT IS ROUNDED TO THE COMPUTE DTYPE IS ROUNDED BY `ouro_q._held` (a
`reduce_precision` XLA keeps, the same in the forward pass and in a
checkpoint's recomputation): norms' outputs, the projections' and the
convolutions' outputs, the gated output, both residual sums, the dense
FFN. KDA's q and k after their L2 norm, g, beta, the scan and the
output norm are float32 and rounded nowhere. models/mla.py and
models/expert_layer.py round by `astype`, as in the nets that share them
(their programs are pinned).

Recomputation: every block is a `jax.checkpoint` that keeps THE
SELECTION (expert_layer.SELECTION) and nothing else; the scan inside a
KDA block keeps a chunk's start state and its solve and makes the rest
again in a backward rule of its own (ops/chunked_delta_rule.py).

Scopes: `kimi.embed`; `kda` around a KDA mixer, inside it `kda.proj`,
`kda.conv`, `kda.gates`, `kda.scan` (the op's: `kda.scan.intra`,
`kda.scan.carry`, `kda.scan.back`), `kda.out`; `glm.mla` around an MLA
mixer (`glm.mla.scores` inside); `glm.moe` / `glm.dense_ffn`;
`kimi.head`.
Counters (`apply_with_stats`): the expert layer's `expert_rows` and
`topk`, `kda_chunks` (chunks the scan walked, summed over the KDA
layers where a chunk is walked) and `kda_state_rms` (RMS of S after the
last position, mean over the KDA layers: where a state that blew up or
died is seen).

The inference server's protocol is the family's stateless window
(runtime/family.server_apply_fn: windows of any length, so the scan
pads); no benchmark cell drives it. Parameters are float32, cast to the
compute dtype at use; a plain pytree (`embed_tokens`, `layers`, `norm`,
`lm_head`; a KDA layer's names are fla's: `q_proj`, `q_conv1d`, `A_log`,
`f_a_proj`, `f_b_proj`, `dt_bias`, `b_proj`, `g_a_proj`, `g_b_proj`,
`o_norm`, `o_proj`).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from ape_x_dqn_tpu.models import mla as mla_module
from ape_x_dqn_tpu.models.base import dtype_of
from ape_x_dqn_tpu.models.expert_layer import (
    SELECTION, ExpertShare, _balanced_scores, count_params, expert_ffn,
    seeded_params)
from ape_x_dqn_tpu.models.ouro_q import _add, _dot, _held, _norm
# under the name a benchmark test replaces to make a departure
from ape_x_dqn_tpu.models.short_conv import short_conv as _short_conv
from ape_x_dqn_tpu.ops.blockwise_attention import BLOCK_K, BLOCK_Q
from ape_x_dqn_tpu.ops.chunked_delta_rule import CHUNK, chunked_delta_rule

L2_EPS = 1e-6                       # (+) the q/k norm's
A_RANGE = (1.0, 16.0)               # A_log = log U(1, 16), a head
DT_RANGE = (1e-3, 1e-1)             # dt_bias = softplus^-1 of log-uniform
STREAMS = ("q", "k", "v")


def _l2_norm(x32: jax.Array) -> jax.Array:
    return x32 * jax.lax.rsqrt(
        jnp.sum(x32 * x32, axis=-1, keepdims=True) + L2_EPS)


_output_gate = jax.nn.sigmoid       # (+) a sigmoid, not SiLU


class KimiLinearQNet:
    """The net as a value: `init(key, tokens, state)` and
    `apply(params, tokens, state)`; `c` is a configs.KimiLinearConfig."""

    def __init__(self, c: Any, compute_dtype: str = "bfloat16",
                 expert_exchange: bool = False,
                 attn_blocks: tuple[int, int] = (BLOCK_Q, BLOCK_K),
                 kda_chunk: int | None = None):
        """`expert_exchange`, `attn_blocks`: as AfmoeQNet's; `kda_chunk`:
        positions a chunk of the delta rule's scan, by default the op's
        `CHUNK` (tests pass a small one so that a tiny sequence walks
        several)."""
        if c.num_expert_group != 1 or c.topk_group != 1:
            raise NotImplementedError(
                "network.kimi_linear: only num_expert_group = topk_group "
                "= 1 is built (no group stage in the expert selection)")
        if not c.mla_use_nope:
            raise NotImplementedError(
                "network.kimi_linear.mla_use_nope=False: only the "
                "published attention without rotation is built")
        self.c = c
        self.compute_dtype = compute_dtype
        self.attn_blocks = attn_blocks
        self.kda_chunk = kda_chunk or CHUNK
        self.num_actions = c.vocab_size // (c.vocab_shard_count
                                            or c.shard_count)
        self.experts_held = c.num_experts // c.shard_count
        self.first_expert = c.shard_index * self.experts_held
        self.router_trains = c.shard_count == 1 or expert_exchange
        self.share = ExpertShare(
            experts=c.num_experts, top_k=c.num_experts_per_token,
            held=self.experts_held, first=self.first_expert,
            norm_topk=c.moe_renormalize, scale=c.routed_scaling_factor,
            router_trains=self.router_trains)
        layers = range(1, c.num_hidden_layers + 1)
        # one kind per layer held: "mla" or "kda"
        self.layer_kinds = tuple(
            "mla" if l in c.full_attn_layers else "kda" for l in layers)
        self.num_kda_layers = sum(k == "kda" for k in self.layer_kinds)
        self.num_dense_layers = min(c.first_k_dense_replace,
                                    c.num_hidden_layers)
        self.kda_width = c.linear_num_heads * c.linear_head_dim
        self.mla_sizes = mla_module.MlaSizes(
            heads=c.num_attention_heads, nope=c.qk_nope_head_dim,
            rope=c.qk_rope_head_dim, v_dim=c.v_head_dim,
            kv_rank=c.kv_lora_rank, eps=c.rms_norm_eps, rope_theta=None)

    # -- parameters --------------------------------------------------------

    def param_shapes(self) -> dict:
        """The parameter pytree as shapes (matrices are [in, out], a
        layer's held experts stacked on a leading axis)."""
        c, h = self.c, self.c.hidden_size
        heads, d, width = c.linear_num_heads, c.linear_head_dim, self.kda_width
        k = c.linear_short_conv_kernel_size

        def ffn(mid, lead=()):
            return {"gate_proj": (*lead, h, mid),
                    "up_proj": (*lead, h, mid),
                    "down_proj": (*lead, mid, h)}

        kda = {
            **{f"{s}_proj": (h, width) for s in STREAMS},
            **{f"{s}_conv1d": (k, width) for s in STREAMS},
            "A_log": (heads,), "dt_bias": (width,),
            "f_a_proj": (h, d), "f_b_proj": (d, width),
            "b_proj": (h, heads),
            "g_a_proj": (h, d), "g_b_proj": (d, width),
            "o_norm": (d,), "o_proj": (width, h)}
        mixers = {"kda": kda,
                  "mla": mla_module.param_shapes(h, self.mla_sizes, None)}
        moe = {"gate": (h, c.num_experts),
               "e_score_correction_bias": (c.num_experts,),
               "experts": ffn(c.moe_intermediate_size,
                              (self.experts_held,)),
               "shared_experts": ffn(
                   c.moe_intermediate_size * c.num_shared_experts)}
        layers = [
            {"input_layernorm": (h,), **mixers[kind],
             "post_attention_layernorm": (h,),
             "mlp": (ffn(c.intermediate_size)
                     if index < self.num_dense_layers else moe)}
            for index, kind in enumerate(self.layer_kinds)]
        return {"embed_tokens": (self.num_actions, h), "layers": layers,
                "norm": (h,), "lm_head": (h, self.num_actions)}

    def param_count(self) -> int:
        return count_params(self.param_shapes())

    def sequence_state_bytes(self, batch_size: int, positions: int) -> int:
        """What a prefix of `positions` leaves, for the HBM fits-check
        (runtime/family.hbm_price): a KDA layer the same whatever
        `positions` is, an MLA layer a latent row per position."""
        c = self.c
        kda = (4 * c.linear_num_heads * c.linear_head_dim ** 2
               + 2 * 3 * (c.linear_short_conv_kernel_size - 1)
               * self.kda_width)
        mla = 2 * positions * (c.kv_lora_rank + c.qk_rope_head_dim)
        return batch_size * (
            self.num_kda_layers * kda
            + (len(self.layer_kinds) - self.num_kda_layers) * mla)

    def step_transient_bytes(self, batch_size: int,
                             trained_steps: int) -> int:
        """What a train step holds beside the persistent state (16 B a
        parameter), for the HBM fits-check: AfmoeQNet's two moments (its
        docstring), and in the second a KDA block's own float32 streams
        beside the expert block's buffers - q, k, v, g, the scan's
        output and their cotangents, [tokens, heads x d] each (40 B a
        token and channel). Anchor (PR 46, published widths, 5 layers,
        batch 1 x 3,072 trained; PERF.md section 4): compiled for a
        described v5e the step's temp is 2.62 GiB, this gives 2.93."""
        c = self.c
        tokens = batch_size * trained_steps
        logits = tokens * self.num_actions * 4
        block = tokens * max(
            c.hidden_size * (12 * c.num_experts_per_token + 8),
            40 * self.kda_width)
        return max(2 * logits, 4 * self.param_count() + block)

    def init(self, key: jax.Array, tokens: Any = None,
             state: Any = None) -> dict:
        """Seeded float32 parameters: expert_layer.seeded_params, and a
        KDA layer's two decay parameters as the family's convention for
        such gates has them ((+) `A_log` = log U(1, 16) a head,
        `dt_bias` = softplus^-1 of a log-uniform step in [1e-3, 1e-1] a
        channel). `tokens`/`state` are taken for flax's call shape and
        ignored."""
        del tokens, state
        params = seeded_params(self.param_shapes(), key)
        for index, p in enumerate(params["layers"]):
            if "A_log" not in p:
                continue
            k_a, k_dt = jax.random.split(jax.random.fold_in(key, index))
            p["A_log"] = jnp.log(jax.random.uniform(
                k_a, p["A_log"].shape, jnp.float32, *A_RANGE))
            dt = jnp.exp(jax.random.uniform(
                k_dt, p["dt_bias"].shape, jnp.float32,
                *(math.log(x) for x in DT_RANGE)))
            p["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
        return params

    # -- the layers --------------------------------------------------------

    def _kda(self, p: dict, u: jax.Array, cache):
        """u = RMSNorm(x) [B, T, hidden] -> (the mixer's output [B, T,
        hidden], (S, conv_tail), chunks walked)."""
        c, dt = self.c, u.dtype
        b, t, _ = u.shape
        heads, d, width = c.linear_num_heads, c.linear_head_dim, self.kda_width
        taps = c.linear_short_conv_kernel_size
        f32 = jnp.float32
        per_head = lambda x: x.reshape(b, t, heads, d)     # noqa: E731
        with jax.named_scope("kda.proj"):
            raw = [_dot(u, p[f"{s}_proj"]) for s in STREAMS]
        with jax.named_scope("kda.conv"):
            tail = (jnp.zeros((b, len(STREAMS), taps - 1, width), dt)
                    if cache is None else cache[1].astype(dt))
            streams, tails = [], []
            for i, s in enumerate(STREAMS):
                seen = jnp.concatenate([tail[:, i], raw[i]], axis=1)
                mixed = _short_conv(seen, p[f"{s}_conv1d"], t)
                streams.append(_held(jax.nn.silu(mixed), dt))
                tails.append(seen[:, t:])
            new_tail = jnp.stack(tails, axis=1)
        with jax.named_scope("kda.gates"):
            q = _l2_norm(per_head(streams[0].astype(f32))) * d ** -0.5
            k = _l2_norm(per_head(streams[1].astype(f32)))
            v = per_head(streams[2].astype(f32))
            step = jnp.dot(_dot(u, p["f_a_proj"]), p["f_b_proj"].astype(dt),
                           preferred_element_type=f32) + p["dt_bias"]
            g = -jnp.exp(p["A_log"])[:, None] * per_head(
                jax.nn.softplus(step))
            beta = jax.nn.sigmoid(jnp.dot(
                u, p["b_proj"].astype(dt), preferred_element_type=f32))
        o, state, walked = chunked_delta_rule(
            q, k, v, g, beta, None if cache is None else cache[0],
            chunk=self.kda_chunk, with_chunks=True)
        with jax.named_scope("kda.out"):
            var = jnp.mean(o * o, axis=-1, keepdims=True)
            o = o * jax.lax.rsqrt(var + c.rms_norm_eps) * p["o_norm"]
            gate = _output_gate(jnp.dot(
                _dot(u, p["g_a_proj"]), p["g_b_proj"].astype(dt),
                preferred_element_type=f32))
            y = _dot(_held(o.reshape(b, t, width) * gate, dt), p["o_proj"])
        return y, (state, new_tail), walked

    def _dense_ffn(self, p: dict, y: jax.Array) -> jax.Array:
        f32 = jnp.float32
        gate, up = _dot(y, p["gate_proj"]), _dot(y, p["up_proj"])
        mid = _held(jax.nn.silu(gate.astype(f32)) * up.astype(f32), y.dtype)
        return _dot(mid, p["down_proj"])

    def _block(self, p: dict, x: jax.Array, cache, tokens: jax.Array,
               layer: int):
        c, dt, eps = self.c, x.dtype, self.c.rms_norm_eps
        kind = self.layer_kinds[layer]
        u = _norm(x, p["input_layernorm"], eps)
        walked = jnp.int32(0)
        rms = jnp.float32(0.0)
        if kind == "kda":
            seen = jnp.int32(0) if cache is None else cache[2]
            with jax.named_scope("kda"):
                mixed, kept, walked = self._kda(p, u, cache)
            rms = jnp.sqrt(jnp.mean(jnp.square(kept[0])))
            cache = (*kept, seen + x.shape[1])
        else:
            seen = 0 if cache is None else cache[0].shape[1]
            with jax.named_scope("glm.mla"):
                mixed, cache = mla_module.mla(
                    p, u, cache, dt, self.mla_sizes, self.attn_blocks)
        x = _add(x, mixed)
        y = _norm(x, p["post_attention_layernorm"], eps)
        if "experts" in p["mlp"]:
            with jax.named_scope("glm.moe"):
                balanced = None
                if c.force_balanced_routing:
                    balanced = _balanced_scores(
                        tokens, seen + jnp.arange(x.shape[1]), layer,
                        c.num_experts)
                ffn, rows, ids = expert_ffn(p["mlp"], y, dt, self.share,
                                            balanced)
            stats = (rows, ids)
        else:
            with jax.named_scope("glm.dense_ffn"):
                ffn = self._dense_ffn(p["mlp"], y)
            stats = None
        return _add(x, ffn), cache, stats, (walked, rms)

    # -- entry points ------------------------------------------------------

    def apply_with_stats(self, params: dict, tokens: jax.Array,
                         state: Any = ()):
        """-> (q [B, T, A] float32, state, stats): `expert_rows` [expert
        layers, held] int32, `topk` [expert layers, B, T, k], `kda_chunks`
        int32 and `kda_state_rms` float32 (the module docstring)."""
        c = self.c
        dt = dtype_of(self.compute_dtype)
        caches = list(state) if state else [None] * c.num_hidden_layers
        tokens = tokens.astype(jnp.int32)
        with jax.named_scope("kimi.embed"):
            x = _held(params["embed_tokens"][tokens], dt)
        keep = jax.checkpoint_policies.save_only_these_names(SELECTION)
        new_state, rows, topk = [], [], []
        walked, rms = jnp.int32(0), jnp.float32(0.0)
        for layer, (p, cache) in enumerate(zip(params["layers"], caches)):
            x, cache, stats, (walked_l, rms_l) = jax.checkpoint(
                partial(self._block, layer=layer), policy=keep)(
                p, x, cache, tokens)
            new_state.append(cache)
            walked, rms = walked + walked_l, rms + rms_l
            if stats is not None:
                rows.append(stats[0])
                topk.append(stats[1])
        with jax.named_scope("kimi.head"):
            x = _norm(x, params["norm"], c.rms_norm_eps)
            q = jnp.dot(x, params["lm_head"].astype(dt),
                        preferred_element_type=jnp.float32)
        b, t = tokens.shape
        stats = {
            "expert_rows": (jnp.stack(rows) if rows else jnp.zeros(
                (0, self.experts_held), jnp.int32)),
            "topk": (jnp.stack(topk) if topk else jnp.zeros(
                (0, b, t, c.num_experts_per_token), jnp.int32)),
            "kda_chunks": walked,
            "kda_state_rms": rms / max(self.num_kda_layers, 1)}
        return q, tuple(new_state), stats

    def apply(self, params: dict, tokens: jax.Array, state: Any = ()):
        q, state, _ = self.apply_with_stats(params, tokens, state)
        return q, state
