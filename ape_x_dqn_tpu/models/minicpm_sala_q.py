"""MiniCPM-SALA's hybrid decoder as a token-level Q-network of the
sequence family, the seventh decoder kind and the first whose state
cannot ride a query: per sequence a lightning layer keeps a float32
`[heads, d, d]` matrix (2 MiB at the published widths) and a sparse
layer its keys, values and compressed keys, 2,112 B a position. Tokens
in, Q(s_t, .) = the model's own untied head over the whole vocabulary.

    apply(params, tokens[B, T] int32, state) -> (q[B, T, A] f32, state)
    extend(params, slot_state, inputs)       -> (outputs, slot_state)

ONE set of layer functions (`_run`), two entries. `apply` is the
family's: `state` is what a burn-in prefix left (`()` for none; the
loss stops its gradient), a dict of the sparse layers' `k`, `v` [B, S,
G, d] and `ck`, the lightning layers' matrices and `seen` (an empty
array whose first dimension says how many positions came before). `extend` is
the inference server's (runtime/family.server_apply_fn,
parallel/inference_server.py): `slot_state` lives on the device
between queries - `len` [slots + 1], per lightning layer `[slots + 1,
heads, d, d]` float32, per sparse layer pools `k`, `v` [G, positions,
d] and `ck` [G, positions / stride, d] in which a session owns one
contiguous range of blocks the host handed out - and `inputs` says,
per row of the batch, `obs` (the new tokens, [B] for a decode step or
[B, n] for a prefill chunk of which `n_valid` [B] count), `slot`,
`base` (the range's first block) and `fresh` (an episode's first
query: the slot's counts and matrices are zeroed INSIDE the program).
The last slot and the pool's tail are scratch: a padding row reads and
writes there. `outputs`: `q` [B, A] at each row's last valid position,
`sel` [B, n, sparse layers, G, topk] int32 (the blocks each query
attended, -1 for none; meaningful where the context has passed
`sparse_dense_len`) and `counters`. Both entries lay the keys out as
ops/block_select_attention.py's pool: `apply` builds a pool of B
ranges around the prefix it was given and takes it apart again.

The equations (benchmarks/reference/minicpm_sala_q.py writes them again
in float32, independently). What the catalog's config.json keys state
is as published; what they leave open is marked (+) and listed under
`assumed` in the benchmark's configuration file.

- x0 = scale_emb E[token]; block, both kinds: x = x + r Mixer(N1(x)),
  x = x + r MLP(N2(x)), r = scale_depth / sqrt(depth_scale_layers)
  (the PUBLISHED depth); RMSNorm eps 1e-6; MLP W_d[silu(W_g y) * W_u
  y]; Q = (N(x) / (hidden / dim_model_base)) W_head.
- lightning-attn: q = RoPE(n_q(y W_q)), k = RoPE(n_k(y W_k)), v = y W_v
  (RMSNorm per head with a learned scale; RoPE theta, half-split, every
  dim); per head S_t = lambda_h S_{t-1} + k_t^T v_t, o_t = q_t S_t /
  sqrt(d), (+) lambda_h = exp(-2^(-8 (h + 1) / heads)) in every layer;
  out = W_o[N_o(o) * sigmoid(y W_gate)], N_o over the concatenated
  heads. ops/lightning_attention.py: `step_slots` for a decode step, on
  the slot pool in place; `chunked` for a prefill chunk and the learner,
  between a gather and a scatter of the rows' matrices.
- minicpm4: q = n_q(y W_q), k = n_k(y W_k), v = y W_v, no position
  encoding; attention as ops/block_select_attention.py's docstring
  says; out = W_o[o * sigmoid(y W_gate)].

Every value held in the compute dtype is rounded by `ouro_q._held`;
softmax, decays and the lightning state are float32. Parameters are
float32, cast at use; the server may hand `extend` parameters already
rounded to the compute dtype (benchmarks: `update_params` of a
bfloat16 copy), which the cast leaves as they are.

Scopes: `sala.embed`; `sala.lightning` with `.proj`, `.state`, `.out`
inside; `sala.sparse` with `.proj`, `.compress`, `.select`, `.attend`
(the gathered blocks of a decode step), `.dense` (the tile walk of a
prefill chunk or the learner's pass, and a decode step's list of every
block while a context is short), `.out`; `sala.mlp`; `sala.head`;
`slots.read` / `slots.write` around whatever moves slot state, but a
decode step's lightning matrices: the kernel under `sala.lightning.state`
reads and writes them where they lie.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ape_x_dqn_tpu.models.base import dtype_of
from ape_x_dqn_tpu.models.expert_layer import count_params, seeded_params
from ape_x_dqn_tpu.models.ouro_q import _add, _dot, _held, _norm
from ape_x_dqn_tpu.ops import block_select_attention as bsa
from ape_x_dqn_tpu.ops import lightning_attention as la

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
# a prefill chunk's sparse attention: queries a tile, positions a key
# tile (ops/block_select_attention.attend_tiles)
TILE_Q, TILE_K = 512, 1024


def _rope_rows(x32: jax.Array, positions: jax.Array, theta: float):
    """x [B, n, H, d] float32, positions [B, n] (each row its own) ->
    rotated, half-split pairing."""
    d = x32.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x32[..., :d // 2], x32[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


class MiniCpmSalaQNet:
    """The net as a value; `s` is a configs.MiniCpmSalaConfig."""

    def __init__(self, s: Any, compute_dtype: str = "bfloat16",
                 expert_exchange: bool = False):
        """`expert_exchange`: taken for the family's constructor call
        and ignored (there is no expert layer to exchange)."""
        del expert_exchange
        if len(s.mixer_types) != s.num_hidden_layers or set(
                s.mixer_types) - {SPARSE, LIGHTNING}:
            raise ValueError(
                f"network.minicpm_sala.mixer_types must name "
                f"num_hidden_layers={s.num_hidden_layers} layers, each "
                f"{SPARSE!r} or {LIGHTNING!r}; got {s.mixer_types}")
        if (s.num_attention_heads % s.num_key_value_heads
                or s.lightning_nkv != s.lightning_nh):
            raise ValueError(
                f"network.minicpm_sala: num_key_value_heads="
                f"{s.num_key_value_heads} must divide num_attention_heads="
                f"{s.num_attention_heads}, and lightning_nkv="
                f"{s.lightning_nkv} must equal lightning_nh="
                f"{s.lightning_nh} (only the published ungrouped lightning "
                f"layer is built)")
        if not (s.qk_norm and s.use_output_gate and s.use_output_norm
                and s.attn_use_output_gate and s.lightning_use_rope
                and not s.attn_use_rope):
            raise NotImplementedError(
                "network.minicpm_sala: only the published switches are "
                "built (qk_norm, both output gates, the lightning output "
                "norm and RoPE, no RoPE in the sparse layers)")
        self.s = s
        self.compute_dtype = compute_dtype
        self.num_actions = s.vocab_size
        self.sparse = bsa.Sizes(
            s.sparse_block_size, s.sparse_kernel_size,
            s.sparse_kernel_stride, s.sparse_init_blocks,
            s.sparse_window_size, s.sparse_topk, s.sparse_dense_len)
        self.sparse.check()
        self.kinds = tuple(s.mixer_types)
        self.num_sparse = self.kinds.count(SPARSE)
        self.num_lightning = self.kinds.count(LIGHTNING)
        self.residual_scale = s.scale_depth / math.sqrt(s.depth_scale_layers)

    # -- parameters --------------------------------------------------------

    def param_shapes(self) -> dict:
        """The parameter pytree as shapes (matrices are [in, out])."""
        s, h = self.s, self.s.hidden_size
        mlp = {"gate_proj": (h, s.intermediate_size),
               "up_proj": (h, s.intermediate_size),
               "down_proj": (s.intermediate_size, h)}

        def mixer(heads, kv_heads, d, out_norm):
            p = {"q_proj": (h, heads * d), "k_proj": (h, kv_heads * d),
                 "v_proj": (h, kv_heads * d), "o_gate": (h, heads * d),
                 "o_proj": (heads * d, h), "q_norm": (d,), "k_norm": (d,)}
            return {**p, "o_norm": (heads * d,)} if out_norm else p

        kinds = {
            SPARSE: mixer(s.num_attention_heads, s.num_key_value_heads,
                          s.head_dim, False),
            LIGHTNING: mixer(s.lightning_nh, s.lightning_nkv,
                             s.lightning_head_dim, True)}
        return {"embed_tokens": (self.num_actions, h),
                "layers": [{"input_layernorm": (h,),
                            "self_attn": kinds[kind],
                            "post_attention_layernorm": (h,), "mlp": mlp}
                           for kind in self.kinds],
                "norm": (h,), "lm_head": (h, self.num_actions)}

    def param_count(self) -> int:
        return count_params(self.param_shapes())

    def init(self, key: jax.Array, tokens: Any = None,
             state: Any = None) -> dict:
        """Seeded float32 parameters (expert_layer.seeded_params: norm
        gains 1, every matrix normal(0, 0.02)). `tokens`/`state` are
        taken for flax's call shape and ignored."""
        del tokens, state
        return seeded_params(self.param_shapes(), key)

    # -- what the net says of its memory -----------------------------------

    def _position_bytes(self) -> int:
        """Bytes one position holds in the sparse layers' pools: keys,
        values, and a compressed key every `stride`."""
        s, two = self.s, jnp.dtype(dtype_of(self.compute_dtype)).itemsize
        row = s.num_key_value_heads * s.head_dim * two
        return self.num_sparse * (2 * row + row // self.sparse.stride)

    def _matrix_bytes(self) -> int:
        s = self.s
        return self.num_lightning * s.lightning_nh * s.lightning_head_dim ** 2 * 4

    def slot_state_bytes(self, slots: int, pool_tokens: int,
                         max_len: int) -> int:
        """What `slot_state(slots, pool_tokens, max_len)` holds on the
        device: runtime/family.hbm_price hands it to check_hbm_fits for
        a net the server keeps in slots."""
        blocks = self._pool_blocks(pool_tokens, max_len)
        return ((slots + 1) * (self._matrix_bytes() + 4)
                + blocks * self.sparse.block * self._position_bytes())

    def sequence_state_bytes(self, batch_size: int, burn_in: int) -> int:
        """What a burn-in prefix leaves, one net (runtime/family.py
        `hbm_price`)."""
        return batch_size * (self._matrix_bytes()
                             + burn_in * self._position_bytes())

    def step_transient_bytes(self, batch_size: int,
                             trained_steps: int) -> int:
        """What a train step holds beside the persistent state, as
        OuroQNet's: the float32 gradient, three [tokens, actions]
        arrays, the blocks' saved inputs, one block's working set and
        the two nets' pools over the trained steps. No anchor: the
        published widths do not train on one chip (the preset's
        docstring), so only the tiny preset's step was ever compiled."""
        s = self.s
        tokens = batch_size * trained_steps
        logits = 3 * tokens * self.num_actions * 4
        boundaries = len(self.kinds) * tokens * s.hidden_size * 2
        block = tokens * (8 * s.hidden_size + 3 * s.intermediate_size) * 4
        return (4 * self.param_count() + logits + boundaries + block
                + 2 * self.sequence_state_bytes(batch_size, trained_steps))

    # -- the slot state ----------------------------------------------------

    @property
    def slot_block(self) -> int:
        """The unit the host's ledger hands out (parallel/slot_pool.py):
        positions a block of the shared pool holds."""
        return self.sparse.block

    @staticmethod
    def slot_lengths(slot_state: dict) -> jax.Array:
        """[slots + 1] positions each session holds on the device."""
        return slot_state["len"]

    def _max_blocks(self, max_len: int) -> int:
        """Blocks a session of `max_len` positions may own, rounded to
        whole key tiles."""
        per_tile = max(min(TILE_K, max_len) // self.sparse.block, 1)
        blocks = -(-max_len // self.sparse.block)
        return -(-blocks // per_tile) * per_tile

    def _pool_blocks(self, pool_tokens: int, max_len: int) -> int:
        """The pool's blocks: what sessions share, then a tail of one
        longest session that is the scratch range and keeps every slice
        of `max_blocks` from any base inside the array."""
        return -(-pool_tokens // self.sparse.block) + self._max_blocks(max_len)

    def slot_state(self, slots: int, pool_tokens: int, max_len: int) -> dict:
        """Zeros for `slots` sessions (+ the scratch slot) that share
        `pool_tokens` positions, none longer than `max_len`."""
        s, dt = self.s, dtype_of(self.compute_dtype)
        positions = self._pool_blocks(pool_tokens, max_len) * self.sparse.block
        g, d = s.num_key_value_heads, s.head_dim

        def pool(n):
            return jnp.zeros((g, n, d), dt)

        return {
            "len": jnp.zeros(slots + 1, jnp.int32),
            "lightning": tuple(
                jnp.zeros((slots + 1, s.lightning_nh, s.lightning_head_dim,
                           s.lightning_head_dim), jnp.float32)
                for _ in range(self.num_lightning)),
            "k": tuple(pool(positions) for _ in range(self.num_sparse)),
            "v": tuple(pool(positions) for _ in range(self.num_sparse)),
            "ck": tuple(pool(positions // self.sparse.stride)
                        for _ in range(self.num_sparse))}

    # -- the layers --------------------------------------------------------

    def _mlp(self, p: dict, y: jax.Array) -> jax.Array:
        f32 = jnp.float32
        gate, up = _dot(y, p["gate_proj"]), _dot(y, p["up_proj"])
        act = _held(jax.nn.silu(gate.astype(f32)), y.dtype)
        return _dot(_held(act.astype(f32) * up.astype(f32), y.dtype),
                    p["down_proj"])

    def _heads(self, p: dict, u: jax.Array, heads: int, kv_heads: int,
               d: int):
        """-> q [B, n, heads, d], k, v [B, n, kv heads, d], the output
        gate [B, n, heads d]; q and k behind their head norms."""
        b, n, _ = u.shape
        eps = self.s.rms_norm_eps
        q = _norm(_dot(u, p["q_proj"]).reshape(b, n, heads, d),
                  p["q_norm"], eps)
        k = _norm(_dot(u, p["k_proj"]).reshape(b, n, kv_heads, d),
                  p["k_norm"], eps)
        v = _dot(u, p["v_proj"]).reshape(b, n, kv_heads, d)
        gate = _held(jax.nn.sigmoid(
            _dot(u, p["o_gate"]).astype(jnp.float32)), u.dtype)
        return q, k, v, gate

    def _lightning(self, p: dict, u: jax.Array, positions: jax.Array,
                   recur):
        """u = N1(x) [B, n, hidden]; `recur(q, k, v [B, n, H, d], slope,
        scale) -> (o [B, n, H, d] float32, the state after the valid
        positions)` is the recurrence over wherever the caller keeps the
        rows' matrices -> (the mixer's output [B, n, hidden], that
        state)."""
        s, dt, f32 = self.s, u.dtype, jnp.float32
        b, n, _ = u.shape
        heads, d = s.lightning_nh, s.lightning_head_dim
        with jax.named_scope("sala.lightning.proj"):
            q, k, v, gate = self._heads(p, u, heads, s.lightning_nkv, d)
            q = _held(_rope_rows(q.astype(f32), positions, s.rope_theta), dt)
            k = _held(_rope_rows(k.astype(f32), positions, s.rope_theta), dt)
        with jax.named_scope("sala.lightning.state"):
            o, after = recur(q, k, v, la.slopes(heads), 1.0 / math.sqrt(d))
        with jax.named_scope("sala.lightning.out"):
            o = _held(o, dt).reshape(b, n, heads * d)
            o = _norm(o, p["o_norm"], s.rms_norm_eps)
            o = _held(o.astype(f32) * gate.astype(f32), dt)
            return _dot(o, p["o_proj"]), after

    def _sparse(self, p: dict, u: jax.Array, pools: tuple, base: jax.Array,
                before: jax.Array, positions: jax.Array, valid: jax.Array,
                blocks: int, decode: bool, tiles: int | None):
        """u = N1(x) [B, n, hidden], `pools` = this layer's (k, v, ck),
        `base` [B] each row's range (in blocks), `before` [B] positions
        the row held -> (the mixer's output, sel [B, n, G, topk], the
        pools with the new positions)."""
        s, sz, dt, f32 = self.s, self.sparse, u.dtype, jnp.float32
        b, n, _ = u.shape
        g, d = s.num_key_value_heads, s.head_dim
        group = s.num_attention_heads // g
        kpool, vpool, ck = pools
        start = base * sz.block
        with jax.named_scope("sala.sparse.proj"):
            q, k, v, gate = self._heads(p, u, s.num_attention_heads, g, d)
            q = q.reshape(b, n, g, group, d)
        with jax.named_scope("slots.write"):
            at = start[:, None] + positions
            kpool = bsa.write(kpool, k, at, valid)
            vpool = bsa.write(vpool, v, at, valid)
        with jax.named_scope("sala.sparse.compress"):
            ck = bsa.compress(ck, kpool, start, before,
                              before + valid.sum(axis=1), n, sz)
        windows = blocks * sz.per

        def own_ck(row_base):
            return jax.lax.dynamic_slice_in_dim(
                ck, row_base * sz.per, windows, 1)

        if decode:
            t = positions[:, 0]
            with jax.named_scope("sala.sparse.select"):
                sel = jax.vmap(
                    lambda qr, cr, tr: bsa.select(qr, cr, tr, sz, blocks))(
                        q, jax.vmap(own_ck)(base), t[:, None])[:, 0]
            short = t < sz.dense_len

            def gathered(_):
                with jax.named_scope("sala.sparse.attend"):
                    return bsa.attend_gathered(q[:, 0], kpool, vpool, start,
                                               sel, t, sz)

            def listed(_):
                # some row's context is still short: every row attends
                # a list of dense_len / block blocks, a short row's all
                # its blocks, a long row's its selection and -1s
                with jax.named_scope("sala.sparse.dense"):
                    count = max(sz.dense_len // sz.block, sz.topk)
                    every = jnp.broadcast_to(
                        bsa.dense_blocks(t, count, sz)[:, None], (b, g, count))
                    chosen = jnp.pad(sel, ((0, 0), (0, 0),
                                           (0, count - sz.topk)),
                                     constant_values=-1)
                    return bsa.attend_gathered(
                        q[:, 0], kpool, vpool, start,
                        jnp.where(short[:, None, None], every, chosen), t, sz)

            o = jax.lax.cond(jnp.any(short & valid[:, 0]), listed, gathered,
                             None)[:, None]
            sel = sel[:, None]
        else:
            tile_q = min(TILE_Q, n)
            tile_k = min(TILE_K, blocks * sz.block)
            pad = -n % tile_q
            qs = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * 3)
            ts = jnp.pad(positions, ((0, 0), (0, pad)), mode="edge")
            parts = (n + pad) // tile_q

            def some_queries(args):
                qr, tr, row_base = args
                with jax.named_scope("sala.sparse.select"):
                    sel_r = bsa.select(qr, own_ck(row_base), tr, sz, blocks)
                with jax.named_scope("sala.sparse.dense"):
                    m = jnp.arange(blocks + 1)
                    picked = (jnp.where(sel_r < 0, blocks, sel_r)[..., None]
                              == m).any(axis=-2)[..., :blocks]
                    allowed = jnp.where(
                        (tr + 1 > sz.dense_len)[:, None, None], picked, True)
                    out = bsa.attend_tiles(
                        qr, tr, allowed, kpool, vpool, row_base * sz.block,
                        sz, tile_k, tiles)
                return out, sel_r

            rows = jnp.repeat(base, parts)
            o, sel = jax.lax.map(some_queries, (
                qs.reshape(b * parts, tile_q, g, group, d),
                ts.reshape(b * parts, tile_q), rows))
            o = o.reshape(b, n + pad, g, group, d)[:, :n]
            sel = sel.reshape(b, n + pad, g, sz.topk)[:, :n]
        with jax.named_scope("sala.sparse.out"):
            o = _held(o, dt).reshape(b, n, g * group * d)
            o = _held(o.astype(f32) * gate.astype(f32), dt)
            return _dot(o, p["o_proj"]), sel, (kpool, vpool, ck)

    def _run(self, params: dict, pools: dict, before: jax.Array,
             tokens: jax.Array, n_valid: jax.Array, slot: jax.Array,
             base: jax.Array, fresh: jax.Array, blocks: int,
             decode: bool, tiles: int | None = None):
        """The stack over tokens [B, n]: row b holds `before[b]`
        positions, its matrices at `slot[b]` of `pools["lightning"]`
        (zeros where `fresh[b]`), its range of the sparse pools at block
        `base[b]`; `blocks`: the most a range may hold. -> (the stream
        after the last block [B, n, hidden], sel [B, n, sparse layers,
        G, topk], counters, the pools after the valid tokens)."""
        s, dt = self.s, dtype_of(self.compute_dtype)
        r = self.residual_scale
        tokens = tokens.astype(jnp.int32)
        b, n = tokens.shape
        positions = before[:, None] + jnp.arange(n, dtype=jnp.int32)
        valid = jnp.arange(n)[None, :] < n_valid[:, None]
        with jax.named_scope("sala.embed"):
            x = _held(s.scale_emb
                      * params["embed_tokens"][tokens].astype(jnp.float32),
                      dt)

        def scaled(y):      # r * a sublayer's output, held
            return _held(r * y.astype(jnp.float32), dt)

        matrices, k, v, ck = (list(pools[name]) for name in (
            "lightning", "k", "v", "ck"))
        sels, li, si = [], 0, 0
        for kind, p in zip(self.kinds, params["layers"]):
            u = _norm(x, p["input_layernorm"], s.rms_norm_eps)
            if kind == LIGHTNING:
                with jax.named_scope("sala.lightning"):
                    if decode:
                        # one pass over each row's matrices, in the pool
                        def in_place(q, k, v, slope, scale):
                            o, pool = la.step_slots(
                                matrices[li], slot, fresh, valid[:, 0],
                                q[:, 0], k[:, 0], v[:, 0], slope, scale)
                            return o[:, None], pool

                        out, matrices[li] = self._lightning(
                            p["self_attn"], u, positions, in_place)
                    else:
                        with jax.named_scope("slots.read"):
                            matrix = jnp.where(fresh[:, None, None, None],
                                               0.0, matrices[li][slot])
                        out, after = self._lightning(
                            p["self_attn"], u, positions,
                            lambda q, k, v, slope, scale: la.chunked(
                                q, k, v, matrix, slope, scale, valid))
                        with jax.named_scope("slots.write"):
                            matrices[li] = matrices[li].at[slot].set(after)
                li += 1
            else:
                with jax.named_scope("sala.sparse"):
                    out, sel, (k[si], v[si], ck[si]) = self._sparse(
                        p["self_attn"], u, (k[si], v[si], ck[si]), base,
                        before, positions, valid, blocks, decode, tiles)
                sels.append(sel)
                si += 1
            x = _add(x, scaled(out))
            with jax.named_scope("sala.mlp"):
                y = _norm(x, p["post_attention_layernorm"], s.rms_norm_eps)
                x = _add(x, scaled(self._mlp(p["mlp"], y)))
        sel = jnp.stack(sels, axis=2)            # [B, n, layers, G, topk]
        took, there = bsa.attended(sel, positions[:, :, None], self.sparse)
        there = jnp.broadcast_to(there, took.shape)
        live = valid[:, :, None]
        counters = {
            "sparse_blocks_attended": jnp.sum(jnp.where(live, took, 0)),
            "sparse_blocks_in_context": jnp.sum(jnp.where(live, there, 0)),
            "extend_tokens": jnp.sum(n_valid)}
        return x, sel, counters, {
            "lightning": tuple(matrices), "k": tuple(k), "v": tuple(v),
            "ck": tuple(ck)}

    def _head(self, params: dict, x: jax.Array) -> jax.Array:
        """The stream -> Q float32 over the vocabulary."""
        s = self.s
        with jax.named_scope("sala.head"):
            h = _norm(x, params["norm"], s.rms_norm_eps)
            h = _held(h.astype(jnp.float32)
                      * (s.dim_model_base / s.hidden_size), x.dtype)
            return jnp.dot(h, params["lm_head"].astype(h.dtype),
                           preferred_element_type=jnp.float32)

    # -- entry points ------------------------------------------------------

    def extend(self, params: dict, slot_state: dict, inputs: dict, *,
               max_len: int):
        """The server's entry (module docstring). `inputs["obs"]` [B]
        is a decode step, [B, n] a prefill chunk; `max_len` (static):
        the longest session `slot_state` was made for."""
        tokens = inputs["obs"]
        decode = tokens.ndim == 1
        if decode:
            tokens = tokens[:, None]
        b = tokens.shape[0]
        slot, fresh = inputs["slot"], inputs["fresh"].astype(bool)
        n_valid = (inputs["n_valid"] if "n_valid" in inputs
                   else jnp.ones(b, jnp.int32))
        with jax.named_scope("slots.read"):
            before = jnp.where(fresh, 0, slot_state["len"][slot])
        x, sel, counters, pools = self._run(
            params, slot_state, before, tokens, n_valid, slot,
            inputs["base"], fresh, self._max_blocks(max_len), decode)
        q = self._head(params, x[jnp.arange(b), jnp.maximum(n_valid - 1, 0)])
        with jax.named_scope("slots.write"):
            pools["len"] = slot_state["len"].at[slot].set(before + n_valid)
        return {"q": q, "sel": sel, "counters": counters}, pools

    def apply_with_stats(self, params: dict, tokens: jax.Array,
                         state: Any = ()):
        """-> (q [B, T, A] float32, state, stats): the family's entry.
        `stats` has `block_applications` and `exit_gates` (none: the
        stack runs once), what runtime/family._looped_loss reads of a
        net without an expert layer, and `sel`."""
        s, sz, dt = self.s, self.sparse, dtype_of(self.compute_dtype)
        b, t = tokens.shape
        seen = state["seen"].shape[0] if state else 0
        total = seen + t
        if total > s.max_position_embeddings:
            raise ValueError(
                f"{total} positions in one sequence, but network."
                f"minicpm_sala.max_position_embeddings="
                f"{s.max_position_embeddings}")
        blocks = self._max_blocks(total)
        span = blocks * sz.block
        g, d = s.num_key_value_heads, s.head_dim

        def pool(held, every):      # [B, S / every, G, d] -> [G, B span', d]
            own = span // every
            rows = jnp.zeros((b, own, g, d), dt)
            if held is not None:
                rows = rows.at[:, :held.shape[1]].set(held.astype(dt))
            return rows.transpose(2, 0, 1, 3).reshape(g, b * own, d)

        layers = range(self.num_sparse)
        pools = {
            "k": tuple(pool(state["k"][i] if state else None, 1)
                       for i in layers),
            "v": tuple(pool(state["v"][i] if state else None, 1)
                       for i in layers),
            "ck": tuple(pool(state["ck"][i] if state else None, sz.stride)
                        for i in layers),
            "lightning": (state["lightning"] if state else tuple(
                jnp.zeros((b, s.lightning_nh, s.lightning_head_dim,
                           s.lightning_head_dim), jnp.float32)
                for _ in range(self.num_lightning)))}
        rows = jnp.arange(b, dtype=jnp.int32)
        x, sel, _, pools = self._run(
            params, pools, jnp.full(b, seen, jnp.int32), tokens,
            jnp.full(b, t, jnp.int32), rows, rows * blocks,
            jnp.zeros(b, bool), blocks, decode=False,
            tiles=-(-total // min(TILE_K, span)))
        q = self._head(params, x)

        def rows_of(p, every):      # the pool -> [B, total / every, G, d]
            own = span // every
            return p.reshape(g, b, own, d).transpose(
                1, 2, 0, 3)[:, :total // every]

        new_state = {
            "k": tuple(rows_of(p, 1) for p in pools["k"]),
            "v": tuple(rows_of(p, 1) for p in pools["v"]),
            "ck": tuple(rows_of(p, sz.stride) for p in pools["ck"]),
            "lightning": pools["lightning"],
            # how many positions came before, as a SHAPE: `apply` sizes
            # its pools from it, and a state is a pytree of arrays
            "seen": jnp.zeros((total, 0), jnp.float32)}
        return q, new_state, {
            "block_applications": jnp.int32(len(self.kinds)),
            "exit_gates": jnp.zeros((1, b, t), jnp.float32), "sel": sel}

    def apply(self, params: dict, tokens: jax.Array, state: Any = ()):
        q, state, _ = self.apply_with_stats(params, tokens, state)
        return q, state
