"""AI21-Jamba2-3B's hybrid decoder (`model_type` jamba) as a token-level
Q-network of the sequence family, the eighth decoder kind and the second
the inference server keeps in slots - with the proportions of
MiniCPM-SALA's state REVERSED: per session a Mamba layer keeps a float32
`[d_state, channels]` state and the last `d_conv - 1` rows of its conv's
input (358,400 B a layer at the published widths, 8.89 MiB a session
over 26 layers AT ANY CONTEXT), an attention layer keys and values of
one head, 512 B a position. Most layers' state holds no blocks at all.
Tokens in, Q(s_t, .) = the model's own tied head over the whole
vocabulary.

    apply(params, tokens[B, T] int32, state) -> (q[B, T, A] f32, state)
    extend(params, slot_state, inputs)       -> (outputs, slot_state)

ONE set of layer functions (`_run`), two entries, as
models/minicpm_sala_q.py's. `apply` is the family's: `state` is what a
burn-in prefix left (`()` for none), a dict of the Mamba layers' `ssm`
and `conv`, the attention layers' `k`, `v` [B, S, G, d] and `seen`.
`extend` is the inference server's (runtime/family.server_apply_fn,
parallel/inference_server.py): `slot_state` lives on the device between
queries - `len` [slots + 1]; per Mamba layer `ssm` [slots + 1, d_state,
channels] float32 and `conv` [slots + 1, (d_conv - 1) channels] in the
compute dtype, both addressed by ROWS (a gather and a scatter along the
first dimension move no other byte of a pool); per attention layer pools
`k`, `v` [G, positions, d] in which a session owns one contiguous range
of blocks of `kv_block` positions the host handed out
(ops/block_select_attention.py's pool, written by its `write`, walked by
its `attend_tiles` a prefill chunk and its `attend_range` a decode step,
every block allowed) - and
`inputs` says, per row, `obs` ([B] a decode step, [B, n] a prefill chunk
of which `n_valid` [B] count), `slot`, `base` (the range's first block)
and `fresh` (an episode's first query: the row starts from zeros
whatever its slot holds). The last slot and the pool's tail are scratch:
a padding row reads and writes there. `outputs`: `q` [B, A] at each
row's last valid position and `counters`.

The two shapes of work part at the Mamba state, on the input's rank and
on nothing else. A PREFILL CHUNK (and `apply`) gathers the rows' `ssm`
states, runs `selective_scan.chunked` from them and scatters them back.
A DECODE STEP gathers and scatters no state: `_run` hands `_mamba` the
layer's whole `ssm` pool with `slot` and `fresh`, and
`selective_scan.step_slots` reads, advances and writes each row's
`[d_state, channels]` block where it lies (one Pallas kernel a layer,
the pool aliased: where XLA made three passes and a select over `[B,
d_state, channels]` the state moves once, PERF.md section 6, PR 58). The
conv tail is gathered, cut and scattered by rows in both. A decode step's
attention gathers nothing either: `attend_range` walks each row's OWN
range of the key and value pools where it lies, tile by tile up to that
row's own context (one Pallas kernel a layer, the pools inputs only: the
gather to the longest range a session may own copied 2.3 positions for
every one attended, PERF.md section 6, PR 60).

The equations (benchmarks/reference/jamba_q.py writes them again in
float32, independently; what the catalog row's keys leave open is marked
(+) and listed under `assumed` in benchmarks/configs/jamba2_3b_1chip.json):

- x0 = E[token]. Block, both kinds: x = x + Mixer(N1(x)), x = x +
  MLP(N2(x)); RMSNorm eps 1e-6; MLP W_d[silu(W_g y) * W_u y] on every
  layer (`num_experts` 1). (+) layer i is attention iff i %
  attn_layer_period == attn_layer_offset. Q = N(x) E^T (tied). No
  position encoding anywhere.
- Mamba, u [T, hidden]: [x | z] = u W_in; x = silu(conv(x) + b_conv)
  (causal, depthwise, `d_conv` taps: models/short_conv.py's filter);
  [dt | B | C] = x W_x; dt, B, C each through its own RMSNorm with a
  gain; delta = softplus(dt W_dt + b_dt); A = -exp(A_log); the
  selective scan (ops/selective_scan.py) h_t = exp(delta_t (x) A) h_{t-1}
  + (delta_t x_t) (x) B_t, y_t = h_t C_t + D x_t; out = (y silu(z)) W_out.
- Attention: q [T, heads, d], k, v [T, kv heads, d], d = hidden / heads,
  no bias, no rotation, causal softmax at d^-1/2, o W_o.

Every value held in the compute dtype is rounded by `ouro_q._held`;
delta (from the product with W_dt on), the softplus, the exponential, h
and the scan are float32; the conv tail is the stream's type. Parameters
are float32, cast at use; the server may hand `extend` matrices already
rounded to the compute dtype, which the cast leaves as they are.

Scopes: `jamba.embed`; `jamba.mamba` with `.in`, `.conv`, `.gates`
(W_x, the three norms, W_dt, the softplus), `.scan` (the state's and the
conv tail's read from the slot rows, the update, their write; at a
decode step the state's three are the one kernel, which carries the
scope), `.out` inside; `jamba.attn` with `.proj`,
`.attend`, `.out`; `jamba.mlp`; `jamba.head`; `slots.read` /
`slots.write` around whatever moves slot state. Counters (`extend`):
`extend_tokens`; `ssm_rows_updated` (rows with a valid position x Mamba
layers: padding rows are not counted); `ssm_tokens_scanned` (valid
positions x Mamba layers through `selective_scan.chunked`: a prefill
chunk's); `attn_positions_read` (keys each valid query attended, summed
over the attention layers: COUNTED FROM THE MASK THE OP APPLIED -
`attend_tiles`' `counted`, inside `attend_range`'s kernel - so that a
mask one position short reads one key a query less than the positions
sent say); `attn_positions_fetched` (positions the key tiles a decode
step walked brought on chip, summed over EVERY row, a padding row's one
tile too, and over the attention layers; 0 from a prefill chunk: read /
fetched is the share of a decode step's key traffic that is context).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ape_x_dqn_tpu.models.base import dtype_of
from ape_x_dqn_tpu.models.expert_layer import count_params, seeded_params
from ape_x_dqn_tpu.models.ouro_q import _add, _dot, _held, _norm
from ape_x_dqn_tpu.models.short_conv import behind, short_conv
from ape_x_dqn_tpu.ops import block_select_attention as bsa
from ape_x_dqn_tpu.ops import selective_scan

MAMBA, ATTENTION = "mamba", "attention"
# positions a block of the key-value pool (the unit the host's ledger
# hands out), and a prefill chunk's attention: queries a tile, positions
# a key tile (ops/block_select_attention.attend_tiles)
KV_BLOCK = 128
TILE_Q, TILE_K = 512, 1024
# Mamba's own initialisation of the step: delta at b_dt alone is
# log-uniform over this range
DT_MIN, DT_MAX = 1e-3, 1e-1


class JambaQNet:
    """The net as a value; `s` is a configs.JambaConfig."""

    def __init__(self, s: Any, compute_dtype: str = "bfloat16",
                 expert_exchange: bool = False, kv_block: int = KV_BLOCK,
                 attn_tiles: tuple[int, int] = (TILE_Q, TILE_K)):
        """`expert_exchange`: taken for the family's constructor call
        and ignored (there is no expert layer to exchange); `kv_block`,
        `attn_tiles`: the pool's block and the prefill's tiles, smaller
        in tests so that tiny sessions cross them."""
        del expert_exchange
        if (s.num_attention_heads % s.num_key_value_heads
                or s.hidden_size % s.num_attention_heads):
            raise ValueError(
                f"network.jamba: num_key_value_heads="
                f"{s.num_key_value_heads} must divide num_attention_heads="
                f"{s.num_attention_heads}, which must divide hidden_size="
                f"{s.hidden_size}")
        if (s.num_experts != 1 or s.mamba_proj_bias
                or not s.mamba_conv_bias):
            raise NotImplementedError(
                "network.jamba: only the published switches are built "
                "(num_experts 1: a dense MLP on every layer; a conv bias; "
                "no bias on the Mamba projections)")
        if attn_tiles[1] % kv_block:
            raise ValueError(f"kv_block={kv_block} must divide the key "
                             f"tile {attn_tiles[1]}")
        self.s = s
        self.compute_dtype = compute_dtype
        self.num_actions = s.vocab_size
        self.kinds = tuple(
            ATTENTION if i % s.attn_layer_period == s.attn_layer_offset
            else MAMBA for i in range(s.num_hidden_layers))
        self.num_mamba = self.kinds.count(MAMBA)
        self.num_attention = self.kinds.count(ATTENTION)
        self.d_inner = s.mamba_expand * s.hidden_size
        self.head_dim = s.hidden_size // s.num_attention_heads
        self.kv_block, self.attn_tiles = kv_block, attn_tiles
        # the pool as ops/block_select_attention.py addresses it; every
        # block is attended, so nothing but `block` is read
        self._pool = bsa.Sizes(kv_block, kv_block, kv_block, 0, 0, 0, 0)

    # -- parameters --------------------------------------------------------

    def param_shapes(self) -> dict:
        """The parameter pytree as shapes (matrices are [in, out]; the
        conv's filter [taps, channels] as short_conv reads it)."""
        s, h, di = self.s, self.s.hidden_size, self.d_inner
        n, r = s.mamba_d_state, s.mamba_dt_rank
        mlp = {"gate_proj": (h, s.intermediate_size),
               "up_proj": (h, s.intermediate_size),
               "down_proj": (s.intermediate_size, h)}
        mamba = {"in_proj": (h, 2 * di), "conv_weight": (s.mamba_d_conv, di),
                 "conv_bias": (di,), "x_proj": (di, r + 2 * n),
                 "dt_proj": (r, di), "dt_bias": (di,), "A_log": (di, n),
                 "D": (di,), "out_proj": (di, h), "dt_layernorm": (r,),
                 "b_layernorm": (n,), "c_layernorm": (n,)}
        q = s.num_attention_heads * self.head_dim
        kv = s.num_key_value_heads * self.head_dim
        attention = {"q_proj": (h, q), "k_proj": (h, kv), "v_proj": (h, kv),
                     "o_proj": (q, h)}
        return {"embed_tokens": (self.num_actions, h),
                "layers": [{"input_layernorm": (h,),
                            **({"mamba": mamba} if kind == MAMBA
                               else {"self_attn": attention}),
                            "pre_ff_layernorm": (h,), "mlp": mlp}
                           for kind in self.kinds],
                "final_layernorm": (h,)}

    def param_count(self) -> int:
        return count_params(self.param_shapes())

    def init(self, key: jax.Array, tokens: Any = None,
             state: Any = None) -> dict:
        """Seeded float32 parameters: expert_layer.seeded_params (norm
        gains 1, every matrix and the conv's bias normal(0, 0.02)), then
        Mamba's own initialisation of what decides the decays - `A_log`
        = log(1 .. d_state) in every channel, `dt_bias` the inverse
        softplus of a step log-uniform in [DT_MIN, DT_MAX], `D` = 1 - so
        that at seeded weights a channel's memory spans one to a
        thousand tokens. `tokens`/`state` are taken for flax's call
        shape and ignored."""
        del tokens, state
        params = seeded_params(self.param_shapes(), key)
        n, di = self.s.mamba_d_state, self.d_inner
        keys = jax.random.split(jax.random.fold_in(key, 1), self.num_mamba)
        mi = 0
        for kind, layer in zip(self.kinds, params["layers"]):
            if kind != MAMBA:
                continue
            step = jnp.exp(jax.random.uniform(
                keys[mi], (di,), jnp.float32, math.log(DT_MIN),
                math.log(DT_MAX)))
            # (arrays of its own a layer: a learner donates every leaf)
            layer["mamba"].update(
                A_log=jnp.log(jnp.tile(
                    jnp.arange(1, n + 1, dtype=jnp.float32), (di, 1))),
                D=jnp.ones(di, jnp.float32),
                # softplus^-1(step) = step + log(1 - exp(-step))
                dt_bias=step + jnp.log(-jnp.expm1(-step)))
            mi += 1
        return params

    # -- what the net says of its memory -----------------------------------

    def _position_bytes(self) -> int:
        """Bytes one position holds in the attention layers' pools."""
        two = jnp.dtype(dtype_of(self.compute_dtype)).itemsize
        return (self.num_attention * 2 * self.s.num_key_value_heads
                * self.head_dim * two)

    def _session_bytes(self) -> int:
        """Bytes a session holds in the Mamba layers, at any context:
        the float32 state and the conv's tail in the compute dtype."""
        s, two = self.s, jnp.dtype(dtype_of(self.compute_dtype)).itemsize
        return self.num_mamba * self.d_inner * (
            4 * s.mamba_d_state + two * (s.mamba_d_conv - 1))

    def slot_state_bytes(self, slots: int, pool_tokens: int,
                         max_len: int) -> int:
        """What `slot_state(slots, pool_tokens, max_len)` holds on the
        device: runtime/family.hbm_price hands it to check_hbm_fits."""
        blocks = self._pool_blocks(pool_tokens, max_len)
        return ((slots + 1) * (self._session_bytes() + 4)
                + blocks * self.kv_block * self._position_bytes())

    def sequence_state_bytes(self, batch_size: int, burn_in: int) -> int:
        """What a burn-in prefix leaves, one net (runtime/family.py
        `hbm_price`)."""
        return batch_size * (self._session_bytes()
                             + burn_in * self._position_bytes())

    def step_transient_bytes(self, batch_size: int,
                             trained_steps: int) -> int:
        """What a train step holds beside the persistent state, as
        MiniCpmSalaQNet's: the float32 gradient, three [tokens, actions]
        arrays, the blocks' saved inputs, one block's working set (a
        Mamba mixer's scan saves h a chunk) and the two nets' state over
        the trained steps. No anchor: the published widths do not train
        on one chip (the preset's docstring), so only the tiny preset's
        step was ever compiled."""
        s = self.s
        tokens = batch_size * trained_steps
        logits = 3 * tokens * self.num_actions * 4
        boundaries = len(self.kinds) * tokens * s.hidden_size * 2
        block = tokens * (8 * s.hidden_size + 3 * s.intermediate_size
                          + 6 * self.d_inner) * 4
        scan = (tokens // selective_scan.CHUNK + 1) * (
            self.d_inner * s.mamba_d_state * 4)
        return (4 * self.param_count() + logits + boundaries + block + scan
                + 2 * self.sequence_state_bytes(batch_size, trained_steps))

    # -- the slot state ----------------------------------------------------

    @property
    def slot_block(self) -> int:
        """The unit the host's ledger hands out (parallel/slot_pool.py):
        positions a block of the attention layers' shared pool holds.
        The Mamba layers' state has no blocks: a slot is a row."""
        return self.kv_block

    @staticmethod
    def slot_lengths(slot_state: dict) -> jax.Array:
        """[slots + 1] positions each session holds on the device."""
        return slot_state["len"]

    def _max_blocks(self, max_len: int) -> int:
        """Blocks a session of `max_len` positions may own, rounded to
        whole key tiles."""
        per_tile = max(min(self.attn_tiles[1], max_len) // self.kv_block, 1)
        blocks = -(-max_len // self.kv_block)
        return -(-blocks // per_tile) * per_tile

    def _pool_blocks(self, pool_tokens: int, max_len: int) -> int:
        """The pool's blocks: what sessions share, then a tail of one
        longest session that is the scratch range and keeps every slice
        of `max_blocks` from any base inside the array."""
        return -(-pool_tokens // self.kv_block) + self._max_blocks(max_len)

    def slot_state(self, slots: int, pool_tokens: int, max_len: int) -> dict:
        """Zeros for `slots` sessions (+ the scratch slot) that share
        `pool_tokens` positions, none longer than `max_len`."""
        s, dt = self.s, dtype_of(self.compute_dtype)
        positions = self._pool_blocks(pool_tokens, max_len) * self.kv_block
        g, d, di = s.num_key_value_heads, self.head_dim, self.d_inner

        def pools():
            return tuple(jnp.zeros((g, positions, d), dt)
                         for _ in range(self.num_attention))

        return {
            "len": jnp.zeros(slots + 1, jnp.int32),
            "ssm": tuple(jnp.zeros((slots + 1, s.mamba_d_state, di),
                                   jnp.float32)
                         for _ in range(self.num_mamba)),
            "conv": tuple(jnp.zeros((slots + 1, (s.mamba_d_conv - 1) * di),
                                    dt) for _ in range(self.num_mamba)),
            "k": pools(), "v": pools()}

    # -- the layers --------------------------------------------------------

    def _mlp(self, p: dict, y: jax.Array) -> jax.Array:
        f32 = jnp.float32
        gate, up = _dot(y, p["gate_proj"]), _dot(y, p["up_proj"])
        act = _held(jax.nn.silu(gate.astype(f32)), y.dtype)
        return _dot(_held(act.astype(f32) * up.astype(f32), y.dtype),
                    p["down_proj"])

    def _mamba(self, p: dict, u: jax.Array, h: jax.Array, tail: jax.Array,
               valid: jax.Array, decode: bool, slot: jax.Array,
               fresh: jax.Array):
        """u = N1(x) [B, n, hidden], h [B, d_state, channels] float32 and
        tail [B, d_conv - 1, channels] what each row's session held ->
        (the mixer's output [B, n, hidden], h and the tail after each
        row's valid positions). At a decode step h is the layer's whole
        POOL [slots + 1, d_state, channels], in which row b's state is at
        `slot[b]` and counts as zeros where `fresh[b]`, and comes back
        as the pool: the op addresses it in place."""
        s, dt, f32 = self.s, u.dtype, jnp.float32
        b, n, _ = u.shape
        di, ds, r = self.d_inner, s.mamba_d_state, s.mamba_dt_rank
        taps = s.mamba_d_conv
        with jax.named_scope("jamba.mamba.in"):
            xz = _dot(u, p["in_proj"])
            x, z = xz[..., :di], xz[..., di:]
        with jax.named_scope("jamba.mamba.conv"):
            seen = behind(tail, x, taps)             # [B, taps - 1 + n, di]
            x = _held(jax.nn.silu(short_conv(seen, p["conv_weight"], n)
                                  + p["conv_bias"]), dt)
            # the rows before each row's next position: those that end
            # at its last VALID one (a row with none keeps its tail)
            # (whole rows from each row's own offset: a gather of
            # `taps - 1` x channels slices, not one of single elements)
            count = valid.sum(axis=1).astype(jnp.int32)
            tail = jax.vmap(lambda rows, at: jax.lax.dynamic_slice_in_dim(
                rows, at, taps - 1, 0))(seen, count)
            # made HERE: left to the scheduler, every layer's tail was
            # cut at the program's end and a prefill chunk kept 26
            # layers' `seen` alive until then (4 GiB at 8 x 2,048 tokens)
            x, tail = jax.lax.optimization_barrier((x, tail))
        with jax.named_scope("jamba.mamba.gates"):
            proj = _dot(x, p["x_proj"])
            eps = s.rms_norm_eps
            step = _norm(proj[..., :r], p["dt_layernorm"], eps)
            bm = _norm(proj[..., r:r + ds], p["b_layernorm"], eps)
            cm = _norm(proj[..., r + ds:], p["c_layernorm"], eps)
            delta = jax.nn.softplus(
                jnp.dot(step, p["dt_proj"].astype(dt),
                        preferred_element_type=f32) + p["dt_bias"])
            a = -jnp.exp(p["A_log"].astype(f32)).T       # [d_state, di]
        with jax.named_scope("jamba.mamba.scan"):
            if decode:
                y, h = selective_scan.step_slots(
                    h, slot, fresh, valid[:, 0], x[:, 0], delta[:, 0], a,
                    bm[:, 0], cm[:, 0], p["D"])
                y = y[:, None]
            else:
                y, h = selective_scan.chunked(h, x, delta, a, bm, cm,
                                              p["D"], valid)
        with jax.named_scope("jamba.mamba.out"):
            y = _held(y, dt)
            gate = _held(jax.nn.silu(z.astype(f32)), dt)
            y = _held(y.astype(f32) * gate.astype(f32), dt)
            return _dot(y, p["out_proj"]), h, tail

    def _attention(self, p: dict, u: jax.Array, pools: tuple,
                   base: jax.Array, positions: jax.Array, valid: jax.Array,
                   blocks: int, decode: bool, tiles: int | None):
        """u = N1(x) [B, n, hidden], `pools` = this layer's (k, v),
        `base` [B] each row's range (in blocks) -> (the mixer's output,
        the pools with the new positions, the keys the applied mask let
        the valid queries attend, summed, the positions a decode step's
        walk fetched, summed over every row)."""
        s, sz, dt = self.s, self._pool, u.dtype
        b, n, _ = u.shape
        g, d = s.num_key_value_heads, self.head_dim
        group = s.num_attention_heads // g
        kpool, vpool = pools
        start = base * sz.block
        with jax.named_scope("jamba.attn.proj"):
            q = _dot(u, p["q_proj"]).reshape(b, n, g, group, d)
            k = _dot(u, p["k_proj"]).reshape(b, n, g, d)
            v = _dot(u, p["v_proj"]).reshape(b, n, g, d)
        with jax.named_scope("slots.write"):
            at = start[:, None] + positions
            kpool = bsa.write(kpool, k, at, valid)
            vpool = bsa.write(vpool, v, at, valid)
        with jax.named_scope("jamba.attn.attend"):
            tile_k = min(self.attn_tiles[1], blocks * sz.block)
            if decode:
                # each row's own range where it lies, up to its own
                # context: nothing is gathered to `blocks`
                o, keys, fetched = bsa.attend_range(
                    q[:, 0], kpool, vpool, start, positions[:, 0], sz,
                    tile_k, blocks * sz.block // tile_k)
                o, fetched = o[:, None], jnp.sum(fetched)
            else:
                tile_q = min(self.attn_tiles[0], n)
                pad = -n % tile_q
                qs = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * 3)
                ts = jnp.pad(positions, ((0, 0), (0, pad)), mode="edge")
                parts = (n + pad) // tile_q
                allowed = jnp.ones((tile_q, g, blocks), bool)

                def some_queries(args):
                    qr, tr, row_base = args
                    return bsa.attend_tiles(qr, tr, allowed, kpool, vpool,
                                            row_base * sz.block, sz, tile_k,
                                            tiles, counted=True)

                o, keys = jax.lax.map(some_queries, (
                    qs.reshape(b * parts, tile_q, g, group, d),
                    ts.reshape(b * parts, tile_q), jnp.repeat(base, parts)))
                o = o.reshape(b, n + pad, g, group, d)[:, :n]
                fetched = jnp.int32(0)
            # a padding query's count is dropped with its answer
            keys = jnp.sum(jnp.where(valid, keys.reshape(b, -1)[:, :n], 0))
        with jax.named_scope("jamba.attn.out"):
            o = _held(o, dt).reshape(b, n, g * group * d)
            return _dot(o, p["o_proj"]), (kpool, vpool), keys, fetched

    def _run(self, params: dict, pools: dict, before: jax.Array,
             tokens: jax.Array, n_valid: jax.Array, slot: jax.Array,
             base: jax.Array, fresh: jax.Array, blocks: int,
             decode: bool, tiles: int | None = None):
        """The stack over tokens [B, n]: row b holds `before[b]`
        positions, its Mamba state at row `slot[b]` of `pools["ssm"]` /
        `pools["conv"]` (zeros where `fresh[b]`), its range of the
        key-value pools at block `base[b]`; `blocks`: the most a range
        may hold. -> (the stream after the last block [B, n, hidden],
        counters, the pools after the valid tokens)."""
        s, dt = self.s, dtype_of(self.compute_dtype)
        tokens = tokens.astype(jnp.int32)
        b, n = tokens.shape
        taps, di = s.mamba_d_conv, self.d_inner
        positions = before[:, None] + jnp.arange(n, dtype=jnp.int32)
        valid = jnp.arange(n)[None, :] < n_valid[:, None]
        with jax.named_scope("jamba.embed"):
            x = _held(params["embed_tokens"][tokens].astype(jnp.float32), dt)
        ssm, conv, k, v = (list(pools[name]) for name in (
            "ssm", "conv", "k", "v"))
        mi = ai = 0
        keys_read = keys_fetched = jnp.int32(0)
        for kind, p in zip(self.kinds, params["layers"]):
            u = _norm(x, p["input_layernorm"], s.rms_norm_eps)
            if kind == MAMBA:
                with jax.named_scope("jamba.mamba"):
                    with jax.named_scope("jamba.mamba.scan"), \
                            jax.named_scope("slots.read"):
                        # a decode step's state stays where it is: the
                        # scan's kernel reads and writes the pool's rows
                        h = ssm[mi] if decode else jnp.where(
                            fresh[:, None, None], 0.0, ssm[mi][slot])
                        tail = jnp.where(
                            fresh[:, None], 0, conv[mi][slot]).reshape(
                                b, taps - 1, di)
                    out, h, tail = self._mamba(p["mamba"], u, h, tail,
                                               valid, decode, slot, fresh)
                    with jax.named_scope("jamba.mamba.scan"), \
                            jax.named_scope("slots.write"):
                        ssm[mi] = h if decode else ssm[mi].at[slot].set(h)
                        conv[mi] = conv[mi].at[slot].set(
                            tail.reshape(b, -1).astype(conv[mi].dtype))
                mi += 1
            else:
                with jax.named_scope("jamba.attn"):
                    out, (k[ai], v[ai]), keys, fetched = self._attention(
                        p["self_attn"], u, (k[ai], v[ai]), base, positions,
                        valid, blocks, decode, tiles)
                keys_read += keys
                keys_fetched += fetched
                ai += 1
            x = _add(x, out)
            with jax.named_scope("jamba.mlp"):
                y = _norm(x, p["pre_ff_layernorm"], s.rms_norm_eps)
                x = _add(x, self._mlp(p["mlp"], y))
        live = (n_valid > 0).astype(jnp.int32)
        counters = {
            "extend_tokens": jnp.sum(n_valid),
            "ssm_rows_updated": self.num_mamba * jnp.sum(live),
            "ssm_tokens_scanned": (jnp.int32(0) if decode
                                   else self.num_mamba * jnp.sum(n_valid)),
            "attn_positions_read": keys_read,
            "attn_positions_fetched": keys_fetched}
        return x, counters, {"ssm": tuple(ssm), "conv": tuple(conv),
                             "k": tuple(k), "v": tuple(v)}

    def _head(self, params: dict, x: jax.Array) -> jax.Array:
        """The stream [..., hidden] -> Q float32 over the vocabulary:
        the embedding's rows are the head's columns, kept [A, hidden]."""
        with jax.named_scope("jamba.head"):
            h = _norm(x, params["final_layernorm"], self.s.rms_norm_eps)
            return jnp.einsum("...h,ah->...a", h,
                              params["embed_tokens"].astype(h.dtype),
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
        x, counters, pools = self._run(
            params, slot_state, before, tokens, n_valid, slot,
            inputs["base"], fresh, self._max_blocks(max_len), decode)
        q = self._head(params, x[jnp.arange(b), jnp.maximum(n_valid - 1, 0)])
        with jax.named_scope("slots.write"):
            pools["len"] = slot_state["len"].at[slot].set(before + n_valid)
        return {"q": q, "counters": counters}, pools

    def apply_with_stats(self, params: dict, tokens: jax.Array,
                         state: Any = ()):
        """-> (q [B, T, A] float32, state, stats): the family's entry.
        `stats` has `block_applications` and `exit_gates` (none: the
        stack runs once), what runtime/family._looped_loss reads of a
        net without an expert layer."""
        s, sz, dt = self.s, self._pool, dtype_of(self.compute_dtype)
        b, t = tokens.shape
        seen = state["seen"].shape[0] if state else 0
        total = seen + t
        if total > s.max_position_embeddings:
            raise ValueError(
                f"{total} positions in one sequence, but network.jamba."
                f"max_position_embeddings={s.max_position_embeddings}")
        blocks = self._max_blocks(total)
        span = blocks * sz.block
        g, d = s.num_key_value_heads, self.head_dim

        def pool(held):         # [B, S, G, d] -> [G, B span, d]
            rows = jnp.zeros((b, span, g, d), dt)
            if held is not None:
                rows = rows.at[:, :held.shape[1]].set(held.astype(dt))
            return rows.transpose(2, 0, 1, 3).reshape(g, b * span, d)

        layers = range(self.num_attention)
        pools = {
            "k": tuple(pool(state["k"][i] if state else None)
                       for i in layers),
            "v": tuple(pool(state["v"][i] if state else None)
                       for i in layers),
            "ssm": (state["ssm"] if state else tuple(
                jnp.zeros((b, s.mamba_d_state, self.d_inner), jnp.float32)
                for _ in range(self.num_mamba))),
            "conv": (state["conv"] if state else tuple(
                jnp.zeros((b, (s.mamba_d_conv - 1) * self.d_inner), dt)
                for _ in range(self.num_mamba)))}
        rows = jnp.arange(b, dtype=jnp.int32)
        x, _, pools = self._run(
            params, pools, jnp.full(b, seen, jnp.int32), tokens,
            jnp.full(b, t, jnp.int32), rows, rows * blocks,
            jnp.zeros(b, bool), blocks, decode=False,
            tiles=-(-total // min(self.attn_tiles[1], span)))
        q = self._head(params, x)

        def rows_of(p):         # the pool -> [B, total, G, d]
            return p.reshape(g, b, span, d).transpose(1, 2, 0, 3)[:, :total]

        new_state = {
            "k": tuple(rows_of(p) for p in pools["k"]),
            "v": tuple(rows_of(p) for p in pools["v"]),
            "ssm": pools["ssm"], "conv": pools["conv"],
            # how many positions came before, as a SHAPE: `apply` sizes
            # its pools from it, and a state is a pytree of arrays
            "seen": jnp.zeros((total, 0), jnp.float32)}
        return q, new_state, {
            "block_applications": jnp.int32(len(self.kinds)),
            "exit_gates": jnp.zeros((1, b, t), jnp.float32)}

    def apply(self, params: dict, tokens: jax.Array, state: Any = ()):
        q, state, _ = self.apply_with_stats(params, tokens, state)
        return q, state
