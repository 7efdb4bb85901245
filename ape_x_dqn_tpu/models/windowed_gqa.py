"""Grouped-query attention over a cache of TWO KINDS, for the decoder
Q-networks whose layers mix a sliding window with full attention
(models/afmoe_q.py, models/smallthinker_q.py).

A layer's cache is `(k [B, C, kv heads, d], v, seen)`: a full layer
keeps every position (C = seen), a sliding one its last `window - 1`
(what the next query's window can still reach; a longer cache changes
nothing), and `seen` (int32 scalar) is how many positions came before,
which a trimmed cache no longer says. None is no cache. The new tokens
take positions seen .. seen + T - 1. The cache holds k as the attention
read it (after a net's head norms and rotation). What a net does around
the call - norms, RoPE, an output gate - stays the net's own.

The `jax.named_scope`s are `afmoe.attn.sliding` / `afmoe.attn.full`
for EVERY net that calls this (its caller opens `afmoe.attn` around the
projections too). The prefix is historical, as `glm.moe*` is in
models/expert_layer.py: benchmarks/harness/afmoe_scopes.py finds the
scopes by these names and only a `benchmark` PR may edit that file.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ape_x_dqn_tpu.ops.blockwise_attention import blockwise_attention


def positions_after(cache, t: int):
    """-> (`seen`, the positions [t] int32 of t new tokens)."""
    seen = jnp.int32(0) if cache is None else cache[2]
    return seen, seen + jnp.arange(t, dtype=jnp.int32)


def attend(q: jax.Array, k: jax.Array, v: jax.Array, cache,
           window: int | None, blocks: tuple[int, int],
           about_mean: bool = False,
           recompute_delta: bool = False) -> jax.Array:
    """q [B, T, heads, d], k/v [B, T, kv heads, d] of the new positions,
    `cache` = (k, v) of the ones before or None -> [B, T, heads, d].
    `window`: a sliding layer's (the query counts), None for a full one;
    `blocks`: ops/blockwise_attention.py's (query, key) block sizes;
    `about_mean`, `recompute_delta`: its arguments of those names (two
    ways to serve a net without q/k norms, whose keys and values share
    one large vector)."""
    with jax.named_scope("afmoe.attn.full" if window is None
                         else "afmoe.attn.sliding"):
        return blockwise_attention(q, k, v, cache, window=window,
                                   block_q=blocks[0], block_k=blocks[1],
                                   about_mean=about_mean,
                                   recompute_delta=recompute_delta)


def extend(cache, k: jax.Array, v: jax.Array, window: int | None):
    """-> (k, v) with the new positions behind the cached ones, cut to
    what a later query of this kind of layer can reach."""
    if cache is not None:
        k = jnp.concatenate([cache[0].astype(k.dtype), k], axis=1)
        v = jnp.concatenate([cache[1].astype(v.dtype), v], axis=1)
    if window is not None:
        start = max(k.shape[1] - (window - 1), 0)
        k, v = k[:, start:], v[:, start:]
    return k, v
