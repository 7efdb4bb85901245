"""Multi-head latent attention (DeepSeek-V2's), for the decoder
Q-networks that have it (models/glm_moe_q.py, models/kimi_linear_q.py).
Parameterised by sizes (`MlaSizes`) and by what the layer's parameters
hold, never by a model's name.

    mla(p, x, cache, dt, m, blocks) -> (out [B, T, hidden], cache)

`x` [B, T, hidden] is the block's normed input; `cache` = (c_kv [B, S,
kv_rank] AFTER its norm, k_r [B, S, rope] after its rotation) of the S
positions already seen, or None. The new tokens take positions S .. S +
T - 1 and attend to the cache and, causally, to each other; the cache
returned holds S + T positions: a latent row per position, which is
what grows with a prefix (a KDA layer's state beside it does not:
models/kimi_linear_q.py).

- The query: c_q = RMSNorm(x W_qa); q = c_q W_qb where `p` has
  `q_a_proj` (a low rank: GLM-4.7-Flash), else q = x W_q (`q_lora_rank`
  null: Kimi-Linear) -> heads x [q_nope | q_rope].
- x W_kva -> [c_kv | k_r]; c_kv = RMSNorm(c_kv); c_kv W_kvb -> heads x
  [k_nope | v]; k = [k_nope | k_r] with k_r shared by all heads.
- `m.rope_theta` a number: RoPE (every rope dim, no scaling, half-split
  pairing) on q_rope and k_r. None: NO ROTATION ANYWHERE (`mla_use_nope`:
  the "rope" dims are plain dims, and something else carries order).
- score = q . k / sqrt(nope + rope), causal, softmax in float32, out =
  sum p v -> W_o. No biases.

TWO FORMS OF THE SCORES, by `blocks`:
- None: materialised, [B, heads, T, S] float32 (GLM's cell: 512
  positions). The cached LATENTS enter the kv_b projection beside the
  new ones, so W_kvb's gradient counts the prefix's rows too.
- (block_q, block_k): ops/blockwise_attention.py, where 32 heads x
  6,144 x 8,192 float32 scores would be 6.4 GB. After W_kvb every head
  has its own keys (a group of one: the two-nest backward schedule) of
  nope + rope dims and values of `v_dim`, which need not be equal (192
  and 128). The prefix's keys and values enter as that function's
  `cache`, under ITS `stop_gradient`: expanded from the cached latents
  with W_kvb, they are constants of the trained pass, W_kvb's gradient
  counts the new rows only, and the backward pass skips the key blocks
  inside the prefix. benchmarks/reference/kimi_linear_q.py cuts at the
  same place. `recompute_delta`: the net has no q/k norms, so its rows
  share one large vector (ops/blockwise_attention.py says what that
  does to ds, and models/ouro_q.py why this argument and not
  `about_mean`).

Scopes: the caller opens `glm.mla` around the call, `glm.mla.scores` is
opened here for both forms (the blockwise call inside it opens
`attn.bwd.dq` / `attn.bwd.dkv` itself). The prefix is historical, as
`glm.moe*` is: benchmarks/harness/glm_scopes.py finds the scopes by
these names.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ape_x_dqn_tpu.models.expert_layer import _rms_norm, _rope
from ape_x_dqn_tpu.ops.blockwise_attention import blockwise_attention

SCORES_SCOPE = "glm.mla.scores"


class MlaSizes(NamedTuple):
    heads: int
    nope: int                  # qk_nope_head_dim
    rope: int                  # qk_rope_head_dim
    v_dim: int                 # v_head_dim
    kv_rank: int               # kv_lora_rank
    eps: float
    rope_theta: float | None   # None: no rotation


def param_shapes(hidden: int, m: MlaSizes, q_rank: int | None) -> dict:
    """The mixer's own parameters as shapes (HF's names; [in, out])."""
    q_out = m.heads * (m.nope + m.rope)
    query = ({"q_proj": (hidden, q_out)} if q_rank is None else
             {"q_a_proj": (hidden, q_rank), "q_a_layernorm": (q_rank,),
              "q_b_proj": (q_rank, q_out)})
    return {**query,
            "kv_a_proj_with_mqa": (hidden, m.kv_rank + m.rope),
            "kv_a_layernorm": (m.kv_rank,),
            "kv_b_proj": (m.kv_rank, m.heads * (m.nope + m.v_dim)),
            "o_proj": (m.heads * m.v_dim, hidden)}


def mla(p: dict, x: jax.Array, cache, dt, m: MlaSizes,
        blocks: tuple[int, int] | None = None):
    b, t, _ = x.shape
    heads, nope, rope = m.heads, m.nope, m.rope
    seen = 0 if cache is None else cache[0].shape[1]
    positions = seen + jnp.arange(t)

    def rotated(a):
        return a if m.rope_theta is None else _rope(a, positions,
                                                    m.rope_theta)

    if "q_a_proj" in p:
        c_q = _rms_norm(x @ p["q_a_proj"].astype(dt), p["q_a_layernorm"],
                        m.eps)
        q = c_q @ p["q_b_proj"].astype(dt)
    else:
        q = x @ p["q_proj"].astype(dt)
    q = q.reshape(b, t, heads, nope + rope)
    if m.rope_theta is not None:
        q = jnp.concatenate([q[..., :nope], rotated(q[..., nope:])],
                            axis=-1)
    kv_a = x @ p["kv_a_proj_with_mqa"].astype(dt)
    c_kv = _rms_norm(kv_a[..., :m.kv_rank], p["kv_a_layernorm"], m.eps)
    k_rope = rotated(kv_a[..., m.kv_rank:])
    if cache is not None:
        c_kv = jnp.concatenate([cache[0].astype(dt), c_kv], axis=1)
        k_rope = jnp.concatenate([cache[1].astype(dt), k_rope], axis=1)
    s = seen + t
    kv = (c_kv @ p["kv_b_proj"].astype(dt)).reshape(
        b, s, heads, nope + m.v_dim)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_rope[:, :, None, :], (b, s, heads, rope))],
        axis=-1)
    v = kv[..., nope:]
    with jax.named_scope(SCORES_SCOPE):
        if blocks is None:
            scores = jnp.einsum("bthd,bshd->bhts", q, k,
                                preferred_element_type=jnp.float32)
            scores = scores * ((nope + rope) ** -0.5)
            causal = (jnp.arange(s)[None, :]
                      <= positions[:, None])               # [T, S]
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1).astype(dt)
            out = jnp.einsum("bhts,bshd->bthd", probs, v)
        else:
            before = (k[:, :seen], v[:, :seen]) if seen else None
            out = blockwise_attention(
                q, k[:, seen:], v[:, seen:], before, block_q=blocks[0],
                block_k=blocks[1], recompute_delta=True)
    out = out.reshape(b, t, heads * m.v_dim) @ p["o_proj"].astype(dt)
    return out, (c_kv, k_rope)
