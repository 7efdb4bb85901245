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
- MLA: c_q = RMSNorm(x W_qa); q = c_q W_qb -> heads x [q_nope | q_rope].
  x W_kva -> [c_kv | k_r]; c_kv = RMSNorm(c_kv); c_kv W_kvb -> heads x
  [k_nope | v]. RoPE (theta, every rope dim, no scaling, HALF-SPLIT
  pairing: dim i rotates with dim i + d/2, HF's `rotate_half`) on
  q_rope and on k_r, which all heads share. score = (q_nope . k_nope +
  q_rope . k_r) / sqrt(nope + rope), causal, softmax in float32, out =
  sum p v -> W_o. No biases. The cache holds c_kv after its norm and
  k_r after its rotation.
- Expert layer: router in float32, s = sigmoid(x W_g); the top-k of
  s + b are selected (b: a fixed, seeded buffer, never trained:
  `stop_gradient`, so Adam's update of it is exactly 0); their weights
  are the selected s (without b) divided by their sum, times
  routed_scaling_factor. FFN(x) = sum_k w_k E_k(x) + E_shared(x), E(x) =
  W_down(silu(W_gate x) * W_up x). The first `first_k_dense_replace`
  layers are one dense SwiGLU instead.
- The share (GlmMoeConfig.shard_count / shard_index): the router scores
  all n_routed_experts and the weights are normalised over all k
  selected, but only selected experts held here add their w_k E_k(x);
  what absent experts would add is left out and the partial sum goes
  on. A share that runs WITHOUT THE EXCHANGE between the chips that
  share the layer (`expert_exchange` False: parallel/mesh.py has no
  expert axis yet) gives its router no gradient: that gradient is a sum
  over all selected experts, of which one chip has its own term only
  (`_route` says what that term alone does). Embedding and
  head hold vocab_size / shard_count rows; ids, Q-values, argmax and
  the loss are over that slice.
- Forced balanced routing (`GlmMoeConfig.force_balanced_routing`, off
  in every preset; Megatron-LM's `--moe-router-force-load-balancing` is
  the precedent, and like it this is for measuring with random weights
  only): the SELECTION is the top-k of `_balanced_scores`, a fixed
  pseudo-random function of (token id, position, layer, expert),
  instead of the top-k of s + b; the weights are still the selected s,
  normalised and scaled, so the router's arithmetic stays in every
  value. Why it exists: at random weights nearly every hidden state is
  one common direction plus a little of its token, so s + b picks
  nearly the same k experts for every token, how many of those k a
  share holds is a draw of the seed (0 to k), and at Adam 1e-4 the draw
  changes within a hundred steps; the grouped matmuls' cost follows
  the rows routed here, so a step's time did too (PERF.md section 6,
  PR 30). A trained checkpoint's router and its b spread the load;
  this stands in for that and for nothing else.
- The config's one multi-token-prediction layer is NOT built: it serves
  the next-token likelihood in pre-training and speculation in serving;
  a TD loss has neither (HF's modelling code skips those weights too).

How the expert matmuls run: no token is dropped and every shape is
fixed. The k x N assignments are sorted by local expert (not-held ones
last), the rows gathered in that order into a [k N, hidden] buffer —
the worst case, every selection local — and the three matmuls are
`jax.lax.ragged_dot` over the groups (XLA:TPU lowers it to a grouped
matmul kernel), so their cost follows the rows actually routed here
(about k N x held / total), not the buffer. Rows past the last group
are masked to zero and combined with weight 0. The sort is a
permutation, so dispatch and combine are GATHERS both ways
(`_dispatch`, `_combine`: the transpose of a gather by a permutation is
the gather by its inverse); as `x[token]` and `.at[token].add(y)` their
transposes were scatter-adds and the dispatch took 16% of a step where
the matmuls it feeds took 3.4% (PERF.md section 6, PR 30).

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
the rest in the backward pass — all but THE SELECTION (`SELECTION`: the
top-k ids, [N, k] int32), which is kept. The recomputation is another
piece of compiled code than the forward pass and its bfloat16
activations differ in the last bit, so a near-tie between the k-th and
(k+1)-th score fell the other way in a handful of tokens: the backward
pass then sorted those tokens to another expert than the one whose
output the loss had seen. Held to the reference's `jax.grad` on the
v5e, a stack of expert matrices was off by 16-19% of its norm where few
rows were routed here, 5-13 times bfloat16's own error; with the
selection kept, 1.1 times (PERF.md section 6, PR 30).

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

from jax.ad_checkpoint import checkpoint_name

from ape_x_dqn_tpu.models.base import dtype_of

INIT_STD = 0.02          # every matrix: normal(0, 0.02); norms 1
ROUTER_BIAS_STD = 0.1    # the fixed selection bias b: normal(0, 0.1)
STEP_REST = 1 << 30      # a step beside gradients and logits (see below)
SELECTION = "glm.moe.selection"   # the one value a block's recomputation keeps


def _rms_norm(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * g).astype(x.dtype)


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x [B, T, ..., d], positions [T] -> rotated, half-split pairing."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]  # [T,d/2]
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :d // 2], x32[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _swiglu(x: jax.Array, p: dict, dt) -> jax.Array:
    gate = x @ p["gate_proj"].astype(dt)
    up = x @ p["up_proj"].astype(dt)
    return (jax.nn.silu(gate) * up) @ p["down_proj"].astype(dt)


def _balanced_scores(tokens: jax.Array, positions: jax.Array, layer: int,
                     experts: int) -> jax.Array:
    """tokens [B, T] int32, positions [T] -> [B, T, experts] float32
    selection scores for `force_balanced_routing`: no two of a token's
    scores are equal, and their order is a fixed pseudo-random function
    of (token id, position, layer). 32-bit integer arithmetic (murmur3's
    finalizer over a sum of odd multiples), the top 18 bits kept and the
    expert's id below them so that a tie falls to the lower id; 24 bits
    in all, which float32 holds exactly."""
    u = lambda x: jnp.asarray(x, jnp.uint32)  # noqa: E731
    e = jnp.arange(experts, dtype=jnp.uint32)
    h = (u(tokens)[:, :, None] * u(0x9E3779B1)
         + u(positions)[None, :, None] * u(0x85EBCA77)
         + u(layer) * u(0xC2B2AE3D) + e * u(0x27D4EB2F))
    h = (h ^ (h >> 16)) * u(0x85EBCA6B)
    h = (h ^ (h >> 13)) * u(0xC2B2AE35)
    h = h ^ (h >> 16)
    return (((h >> 14) << 6) | (u(experts - 1) - e)).astype(jnp.float32)


@jax.custom_vjp
def _dispatch(x: jax.Array, order: jax.Array, inverse: jax.Array
              ) -> jax.Array:
    """x [N, h] -> [k N, h]: row j is the token of assignment
    `order[j]` (assignment a belongs to token a // k)."""
    return x[order // (order.shape[0] // x.shape[0])]


def _dispatch_fwd(x, order, inverse):
    return _dispatch(x, order, inverse), (inverse, x.shape[0])


def _dispatch_bwd(res, g):
    inverse, n = res
    return g[inverse].reshape(n, -1, g.shape[-1]).sum(axis=1), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _combine(y: jax.Array, order: jax.Array, inverse: jax.Array, n: int
             ) -> jax.Array:
    """y [k N, h] in sorted order -> [N, h]: each token's k rows summed."""
    return y[inverse].reshape(n, -1, y.shape[-1]).sum(axis=1)


def _combine_fwd(y, order, inverse, n):
    return _combine(y, order, inverse, n), order


def _combine_bwd(n, order, g):
    return g[order // (order.shape[0] // n)], None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


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
        self.num_dense_layers = min(g.first_k_dense_replace,
                                    g.num_hidden_layers)
        self.num_moe_layers = g.num_hidden_layers - self.num_dense_layers
        self.router_trains = g.shard_count == 1 or expert_exchange

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
        import math

        return sum(math.prod(s) for s in jax.tree.leaves(
            self.param_shapes(), is_leaf=lambda x: isinstance(x, tuple)))

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
        """Seeded float32 parameters: matrices normal(0, 0.02), norm
        gains 1, the router's selection bias normal(0, 0.1) (a buffer:
        `apply` never lets a gradient reach it). `tokens`/`state` are
        taken for flax's call shape and ignored."""
        del tokens, state
        shapes = self.param_shapes()
        is_shape = lambda x: isinstance(x, tuple)  # noqa: E731
        paths = jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=is_shape)[0]
        keys = jax.random.split(key, len(paths))

        def leaf(path, shape, k):
            name = path[-1].key
            if name.endswith("norm"):
                return jnp.ones(shape, jnp.float32)
            std = (ROUTER_BIAS_STD if name == "e_score_correction_bias"
                   else INIT_STD)
            return std * jax.random.normal(k, shape, jnp.float32)

        leaves = [leaf(path, shape, k)
                  for (path, shape), k in zip(paths, keys)]
        return jax.tree.unflatten(
            jax.tree.structure(shapes, is_leaf=is_shape), leaves)

    # -- the layers --------------------------------------------------------

    def _mla(self, p: dict, x: jax.Array, cache, dt):
        g = self.g
        b, t, _ = x.shape
        heads, nope, rope = (g.num_attention_heads, g.qk_nope_head_dim,
                             g.qk_rope_head_dim)
        seen = 0 if cache is None else cache[0].shape[1]
        positions = seen + jnp.arange(t)
        c_q = _rms_norm(x @ p["q_a_proj"].astype(dt), p["q_a_layernorm"],
                        g.rms_norm_eps)
        q = (c_q @ p["q_b_proj"].astype(dt)).reshape(
            b, t, heads, self.q_head_dim)
        q = jnp.concatenate(
            [q[..., :nope], _rope(q[..., nope:], positions, g.rope_theta)],
            axis=-1)
        kv_a = x @ p["kv_a_proj_with_mqa"].astype(dt)
        c_kv = _rms_norm(kv_a[..., :g.kv_lora_rank], p["kv_a_layernorm"],
                         g.rms_norm_eps)
        k_rope = _rope(kv_a[..., g.kv_lora_rank:], positions, g.rope_theta)
        if cache is not None:
            c_kv = jnp.concatenate([cache[0].astype(dt), c_kv], axis=1)
            k_rope = jnp.concatenate([cache[1].astype(dt), k_rope], axis=1)
        s = seen + t
        kv = (c_kv @ p["kv_b_proj"].astype(dt)).reshape(
            b, s, heads, nope + g.v_head_dim)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_rope[:, :, None, :], (b, s, heads, rope))],
            axis=-1)
        v = kv[..., nope:]
        with jax.named_scope("glm.mla.scores"):
            scores = jnp.einsum("bthd,bshd->bhts", q, k,
                                preferred_element_type=jnp.float32)
            scores = scores * (self.q_head_dim ** -0.5)
            causal = (jnp.arange(s)[None, :]
                      <= positions[:, None])               # [T, S]
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1).astype(dt)
            out = jnp.einsum("bhts,bshd->bthd", probs, v)
        out = out.reshape(b, t, heads * g.v_head_dim) @ p["o_proj"].astype(dt)
        return out, (c_kv, k_rope)

    def _route(self, p: dict, x: jax.Array, balanced):
        """x [N, hidden] -> (top-k expert ids [N, k] int32, their
        weights [N, k] float32, normalised over all k and scaled).
        `balanced` [N, experts]: `_balanced_scores`, which then decide
        the selection, or None for the model's own s + b."""
        g = self.g
        with jax.named_scope("glm.moe.router"):
            s = jax.nn.sigmoid(jnp.dot(
                x.astype(jnp.float32), p["gate"],
                precision=jax.lax.Precision.HIGHEST))
            select = balanced
            if select is None:
                select = s + jax.lax.stop_gradient(
                    p["e_score_correction_bias"])
            _, ids = jax.lax.top_k(select, g.num_experts_per_tok)
            ids = checkpoint_name(ids, SELECTION)
            w = jnp.take_along_axis(s, ids, axis=-1)
            if g.norm_topk_prob:
                w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
            if not self.router_trains:
                # a share without the exchange sees only its own
                # experts' term of the router's gradient (the sum runs
                # over every
                # selected expert, wherever it lives), and that term
                # alone teaches the router to send tokens to the
                # experts that are absent, whose part is left out: on
                # the v5e the rows routed here fell from 9,600 to under
                # 100 a step within 20 steps (PERF.md section 6, PR 30).
                # Until the exchange sums the terms the router is held
                # fixed, as b is
                w = jax.lax.stop_gradient(w)
            return ids.astype(jnp.int32), w * g.routed_scaling_factor

    def _moe(self, p: dict, x: jax.Array, dt, balanced=None):
        """x [B, T, hidden] -> (FFN(x), rows routed to each held expert
        [held] int32, the top-k ids [B, T, k]). `balanced` [B, T,
        experts]: see `_route`."""
        b, t, h = x.shape
        n, k, held = b * t, self.g.num_experts_per_tok, self.experts_held
        flat = x.reshape(n, h)
        ids, w = self._route(
            p, flat, None if balanced is None else balanced.reshape(n, -1))
        with jax.named_scope("glm.moe.dispatch"):
            local = ids.reshape(-1) - self.first_expert          # [k N]
            here = (local >= 0) & (local < held)
            slot = jnp.where(here, local, held)    # not held: sorts last
            order = jnp.argsort(slot, stable=True).astype(jnp.int32)
            inverse = jnp.zeros_like(order).at[order].set(
                jnp.arange(n * k, dtype=jnp.int32))
            rows = jnp.bincount(slot, length=held + 1)[:held].astype(
                jnp.int32)
            live = jnp.arange(n * k) < rows.sum()
            # masked both ways: a row past the last group reads zeros,
            # and whatever the grouped matmul's transpose leaves in its
            # cotangent never reaches the token it was gathered from
            gathered = jnp.where(live[:, None],
                                 _dispatch(flat, order, inverse), 0)
            w_sorted = jnp.where(live, w.reshape(-1)[order], 0.0)
        with jax.named_scope("glm.moe.experts"):
            e = p["experts"]
            gate = jax.lax.ragged_dot(gathered, e["gate_proj"].astype(dt),
                                      rows)
            up = jax.lax.ragged_dot(gathered, e["up_proj"].astype(dt), rows)
            y = jax.lax.ragged_dot(jax.nn.silu(gate) * up,
                                   e["down_proj"].astype(dt), rows)
        with jax.named_scope("glm.moe.dispatch"):
            # rows past the last group are whatever the kernel left
            y = jnp.where(live[:, None], y, 0) * w_sorted[:, None].astype(dt)
            routed = _combine(y, order, inverse, n)
        with jax.named_scope("glm.moe.shared"):
            shared = _swiglu(flat, p["shared_experts"], dt)
        return (routed + shared).reshape(b, t, h), rows, ids.reshape(b, t, k)

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
