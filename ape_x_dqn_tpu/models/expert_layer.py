"""The routed + shared expert layer that the decoder Q-networks share
(models/glm_moe_q.py, models/afmoe_q.py), with the three small functions
every decoder block here is made of (`_rms_norm`, `_rope`, `_swiglu`) and
how their parameters are seeded and counted.
Parameterised by sizes (`ExpertShare`), never by a model's name.

The layer: router in float32, s = sigmoid(x W_g) over ALL routed
experts; the top-k of s + b are selected (b: a fixed, seeded buffer,
never trained: `stop_gradient`, so Adam's update of it is exactly 0);
their weights are the selected s (without b), divided by their sum if
`norm_topk`, times `scale`. FFN(x) = sum_k w_k E_k(x) + E_shared(x),
E(x) = W_down(silu(W_gate x) * W_up x).

The share: the router scores all `experts` and the weights are
normalised over all k selected, but only selected experts with an id in
[first, first + held) add their w_k E_k(x); what absent experts would
add is left out and the partial sum goes on. A share that runs WITHOUT
THE EXCHANGE between the chips that share the layer (`router_trains`
False: parallel/mesh.py has no expert axis yet) gives its router no
gradient: that gradient is a sum over all selected experts, of which
one chip has its own term only (`route` says what that term alone
does).

Forced balanced routing (`balanced` scores from `_balanced_scores`;
Megatron-LM's `--moe-router-force-load-balancing` is the precedent, and
like it this is for measuring with random weights only): the SELECTION
is the top-k of a fixed pseudo-random function of (token id, position,
layer, expert) instead of the top-k of s + b; the weights are still the
selected s, normalised and scaled, so the router's arithmetic stays in
every value. Why it exists: at random weights nearly every hidden state
is one common direction plus a little of its token, so s + b picks
nearly the same k experts for every token, how many of those k a share
holds is a draw of the seed (0 to k), and at Adam 1e-4 the draw changes
within a hundred steps; the grouped matmuls' cost follows the rows
routed here, so a step's time did too (PERF.md section 6, PR 30).

How the expert matmuls run: no token is dropped and every shape is
fixed. The k x N assignments are sorted by local expert (not-held ones
last), the rows gathered in that order into a [k N, hidden] buffer -
the worst case, every selection local - and the three matmuls are
`jax.lax.ragged_dot` over the groups (XLA:TPU lowers it to a grouped
matmul kernel), so their cost follows the rows actually routed here
(about k N x held / experts), not the buffer. Rows past the last group
are masked to zero and combined with weight 0. The sort is a
permutation, so dispatch and combine are GATHERS both ways
(`_dispatch`, `_combine`: the transpose of a gather by a permutation is
the gather by its inverse); as `x[token]` and `.at[token].add(y)` their
transposes were scatter-adds and the dispatch took 16% of a step where
the matmuls it feeds took 3.4% (PERF.md section 6, PR 30).

THE SELECTION (`SELECTION`: the top-k ids, [N, k] int32) is the one
value a block's recomputation keeps (`checkpoint_name`; the nets wrap
each block in `jax.checkpoint(policy=save_only_these_names(SELECTION))`):
the recomputation is another piece of compiled code than the forward
pass and its bfloat16 activations differ in the last bit, so a near-tie
between the k-th and (k+1)-th score fell the other way in a handful of
tokens and the backward pass sorted those tokens to another expert than
the one whose output the loss had seen (PERF.md section 6, PR 30).

The `jax.named_scope`s are `glm.moe.router`, `.dispatch`, `.experts`,
`.shared` for EVERY net that calls this layer (its caller opens
`glm.moe` around it). The prefix is historical - the layer was written
for GLM-4.7-Flash (PR 30) - and stays because
benchmarks/harness/glm_scopes.py finds the scopes by these names and
only a `benchmark` PR may edit that file (PERF.md section 7).
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name

SELECTION = "glm.moe.selection"   # the one value a block's recomputation keeps
INIT_STD = 0.02          # every matrix: normal(0, 0.02); norms 1
ROUTER_BIAS_STD = 0.1    # the fixed selection bias b: normal(0, 0.1)


class ExpertShare(NamedTuple):
    """The sizes of one chip's share of an expert layer."""
    experts: int           # routed experts the router scores (all of them)
    top_k: int
    held: int              # routed experts whose weights live here
    first: int             # id of the first one held
    norm_topk: bool        # divide the selected scores by their sum
    scale: float           # then multiply by this
    router_trains: bool    # False in a share without the exchange


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def count_params(shapes: dict) -> int:
    """Parameters of a pytree of shapes (tuples at the leaves)."""
    return sum(math.prod(s) for s in jax.tree.leaves(shapes,
                                                     is_leaf=_is_shape))


def seeded_params(shapes: dict, key: jax.Array) -> dict:
    """A decoder's float32 parameters from its pytree of shapes:
    matrices normal(0, INIT_STD), norm gains (names ending in `norm`)
    1, the router's selection bias normal(0, ROUTER_BIAS_STD) (a
    buffer: `route` never lets a gradient reach it)."""
    paths = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)[0]
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
        jax.tree.structure(shapes, is_leaf=_is_shape), leaves)


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
    finalizer over a sum of odd multiples), the hash's top bits kept and
    the expert's id below them so that a tie falls to the lower id; 24
    bits in all, which float32 holds exactly: up to 64 experts take 6
    bits for the id and leave the hash 18, more take as many as their
    ids need (128: 7 and 17)."""
    u = lambda x: jnp.asarray(x, jnp.uint32)  # noqa: E731
    id_bits = max((experts - 1).bit_length(), 6)
    e = jnp.arange(experts, dtype=jnp.uint32)
    h = (u(tokens)[:, :, None] * u(0x9E3779B1)
         + u(positions)[None, :, None] * u(0x85EBCA77)
         + u(layer) * u(0xC2B2AE3D) + e * u(0x27D4EB2F))
    h = (h ^ (h >> 16)) * u(0x85EBCA6B)
    h = (h ^ (h >> 13)) * u(0xC2B2AE35)
    h = h ^ (h >> 16)
    return (((h >> (8 + id_bits)) << id_bits)
            | (u(experts - 1) - e)).astype(jnp.float32)


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



def route(p: dict, x: jax.Array, share: ExpertShare, balanced):
    """x [N, hidden] -> (top-k expert ids [N, k] int32, their
    weights [N, k] float32, normalised over all k and scaled).
    `balanced` [N, experts]: `_balanced_scores`, which then decide
    the selection, or None for the model's own s + b."""
    with jax.named_scope("glm.moe.router"):
        s = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), p["gate"],
            precision=jax.lax.Precision.HIGHEST))
        select = balanced
        if select is None:
            select = s + jax.lax.stop_gradient(
                p["e_score_correction_bias"])
        _, ids = jax.lax.top_k(select, share.top_k)
        ids = checkpoint_name(ids, SELECTION)
        w = jnp.take_along_axis(s, ids, axis=-1)
        if share.norm_topk:
            w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
        if not share.router_trains:
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
        return ids.astype(jnp.int32), w * share.scale


def expert_ffn(p: dict, x: jax.Array, dt, share: ExpertShare,
               balanced=None):
    """x [B, T, hidden] -> (FFN(x), rows routed to each held expert
    [held] int32, the top-k ids [B, T, k]). `p`: `gate` [hidden,
    experts], `e_score_correction_bias` [experts], `experts` (the held
    ones' three matrices stacked on a leading axis), `shared_experts`.
    `balanced` [B, T, experts]: see `route`."""
    b, t, h = x.shape
    n, k, held = b * t, share.top_k, share.held
    flat = x.reshape(n, h)
    ids, w = route(
        p, flat, share, None if balanced is None else balanced.reshape(n, -1))
    with jax.named_scope("glm.moe.dispatch"):
        local = ids.reshape(-1) - share.first                # [k N]
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
