"""The routed (and, where the model has one, shared) expert layer that the
decoder Q-networks share (models/glm_moe_q.py, models/afmoe_q.py,
models/smallthinker_q.py), with the three small functions every decoder
block here is made of (`_rms_norm`, `_rope`, `_swiglu`) and how their
parameters are seeded and counted.
Parameterised by sizes (`ExpertShare`) and by arguments, never by a
model's name.

The layer in two parts. THE PLAN (`plan` -> `Plan`) is everything
decided before a row is fetched: the router's logits r = t W_g in
float32 over ALL routed experts, the top-k selection, its weights, the
sort of the k N assignments by held expert, the inverse of that sort
and the rows each held expert gets. `t` is the tensor the router READS,
which need not be the tensor the experts are fed: GLM-4.7-Flash and
Trinity-Mini route from the rows themselves (`expert_ffn` then makes
the plan from x), SmallThinker from the attention's input, ahead of
attention (its block makes the plan and hands it over, `planned=`).
THE APPLICATION (`expert_ffn`) gathers the rows, runs the grouped
matmuls and combines: FFN(x) = sum_k w_k E_k(x) [+ E_shared(x) where
`p` has `shared_experts`], E(x) = W_down(act(W_gate x) * W_up x), `act`
an argument (SiLU; ReLU for SmallThinker's ReGLU).

Two scorings (`route`'s `scoring`):
- SIGMOID: s = sigmoid(r); the top-k of s + b are selected (b: a
  fixed, seeded buffer, never trained: `stop_gradient`, so Adam's
  update of it is exactly 0); their weights are the selected s (without
  b), divided by their sum if `norm_topk`, times `scale`.
- SOFTMAX_SELECTED: the top-k of r are selected; their weights are the
  softmax over those k logits (what a softmax over all experts,
  renormalised over the selected, comes to), times `scale`. No b.

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
layer, expert) instead of the model's own; the weights are still the
scoring's own at the selected experts, so the router's arithmetic -
and the tensor it read - stays in every value. Why it exists: at
random weights nearly every hidden state is one common direction plus
a little of its token, so s + b picks
nearly the same k experts for every token, how many of those k a share
holds is a draw of the seed (0 to k), and at Adam 1e-4 the draw changes
within a hundred steps; the grouped matmuls' cost follows the rows
routed here, so a step's time did too (PERF.md section 6, PR 30).

How the expert matmuls run: no token is dropped and every shape is
fixed. The k x N assignments are sorted by local expert (not-held ones
last; the sort is stable, so a group's rows keep token order) and the
three matmuls are `jax.lax.ragged_dot` over the groups (XLA:TPU lowers
it to a grouped matmul kernel), so their cost follows the rows actually
routed here. The buffers around them do not follow anything: a row
gather costs per row fetched, live or not. So they are sized by
`capacity(share, n)`: the rows a share EXPECTS, k N x held / experts,
times `CAPACITY_SLACK`, rounded up to `ROW_TILE`, never above k N.
- Where the capacity is k N (a whole layer, any share of 2/3 or more)
  there is one path and no branch, `_full_width`: rows gathered in
  sorted order into [k N, hidden], rows past the last group masked to
  zero and combined with weight 0. The sort is a permutation, so
  dispatch and combine are GATHERS both ways (`_dispatch`, `_combine`:
  the transpose of a gather by a permutation is the gather by its
  inverse); as `x[token]` and `.at[token].add(y)` over k N rows their
  transposes were scatter-adds and the dispatch took 16% of a step
  where the matmuls it feeds took 3.4% (PERF.md section 6, PR 30).
- Below that (`_routed`) a step that routes at most C = capacity rows
  here takes `_compact`: the first C sorted assignments' tokens are
  gathered into [C, hidden] and the matmuls, masks and weights run
  over C rows. Combine (and dispatch's transpose) is STILL A GATHER:
  each of the k N assignments fetches its row of the buffer, or the
  row of zeros appended to it if it has none (`_summed_per_token`),
  and a token's k rows are summed. k N fetches again, but all except
  C of them fetch that one row, and the layer's pass took a third of
  the full width's time on the chip; a scatter-add of the C rows took
  as long, and XLA:TPU emits 2.9 MiB of code for each one, which the
  device holds (PERF.md section 6, PR 33 has both readings). A step
  that routes MORE than C rows here takes `_full_width` under a
  `lax.cond` (`_overflow`): the same arithmetic over k N rows; nothing
  is dropped, clipped or deferred.
  The branch spans dispatch -> experts -> combine; the plan (router,
  sort, inverse, counts) stays outside it. `_routed` is a `custom_vjp`
  that keeps only its inputs and branches again in its backward pass:
  differentiating the `cond` itself makes each branch write zeros for
  the other's residuals, the compact one the full path's [k N, .]
  arrays. What the backward branch hands out is copied at once
  (`_routed_bwd` says why).
Either way a row past the last group reads zeros and its cotangent
never reaches a token: `ragged_dot`'s transpose leaves those rows
unwritten (the NaN of PR 30), so both sides of the gather are masked.

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


# Rows of buffer for each row a share expects (`capacity`). The cells'
# forced selection puts a step's total within 1% of the expectation
# (fullest expert 1.05-1.07 of the mean); a trained router with a bias
# update spreads more, and a step that passes the capacity pays the
# full width (`_routed`), so the slack buys the compact path a margin
# at half its own cost in rows: at 1.5 a 16-way share's buffers are
# 3/32 of the worst case.
CAPACITY_SLACK = 1.5
ROW_TILE = 128           # the grouped matmul's row tile: the MXU's side


class ExpertShare(NamedTuple):
    """The sizes of one chip's share of an expert layer."""
    experts: int           # routed experts the router scores (all of them)
    top_k: int
    held: int              # routed experts whose weights live here
    first: int             # id of the first one held
    norm_topk: bool        # divide the selected scores by their sum
    scale: float           # then multiply by this
    router_trains: bool    # False in a share without the exchange


def capacity(share: ExpertShare, n: int) -> int:
    """Rows of the routed path's buffers for n tokens: what the share
    expects times `CAPACITY_SLACK`, in whole `ROW_TILE`s, and never
    more than all k n assignments (the worst case, every selection
    local; any share of 2/3 of the experts or more)."""
    worst = share.top_k * n
    tiles = math.ceil(
        CAPACITY_SLACK * worst * share.held / share.experts / ROW_TILE)
    return min(ROW_TILE * tiles, worst)


def fits(rows: jax.Array, c: int) -> jax.Array:
    """rows [..., held] routed to each held expert -> whether they fit
    buffers of c rows: the routed path's branch, and what the family's
    `moe_compact_share` counts."""
    return rows.sum(axis=-1) <= c


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


def _summed_per_token(y: jax.Array, back: jax.Array, n: int) -> jax.Array:
    """y [C, h], the rows of a buffer -> [N, h]: each token's k
    assignments' rows summed. `back` [k N]: an assignment's row in the
    buffer, C for one that has none (it reads a row of zeros)."""
    rows = jnp.concatenate([y, jnp.zeros((1, y.shape[1]), y.dtype)])
    return rows[back].reshape(n, -1, y.shape[1]).sum(axis=1)


@jax.custom_vjp
def _dispatch_some(x: jax.Array, token: jax.Array, back: jax.Array
                   ) -> jax.Array:
    """x [N, h] -> [C, h]: row j is token `token[j]`."""
    return x[token]


def _dispatch_some_fwd(x, token, back):
    return x[token], (back, x.shape[0])


def _dispatch_some_bwd(res, g):
    back, n = res
    return _summed_per_token(g, back, n), None, None


_dispatch_some.defvjp(_dispatch_some_fwd, _dispatch_some_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _combine_some(y: jax.Array, token: jax.Array, back: jax.Array, n: int
                  ) -> jax.Array:
    """y [C, h] -> [N, h]: `_summed_per_token`."""
    return _summed_per_token(y, back, n)


def _combine_some_fwd(y, token, back, n):
    return _summed_per_token(y, back, n), token


def _combine_some_bwd(n, token, g):
    return g[token], None, None


_combine_some.defvjp(_combine_some_fwd, _combine_some_bwd)



SIGMOID = "sigmoid"                     # s = sigmoid(x W_g), top-k of s + b
SOFTMAX_SELECTED = "softmax_selected"   # top-k of x W_g, softmax over those k


def route(p: dict, x: jax.Array, share: ExpertShare, balanced,
          scoring: str = SIGMOID):
    """x [N, hidden], the tensor the router reads (the rows the experts
    are fed, or another: the module docstring's "plan") -> (top-k
    expert ids [N, k] int32, their weights [N, k] float32, times
    `share.scale`). `scoring`: SIGMOID (weights the selected s,
    normalised over all k if `share.norm_topk`; the selection is the
    top-k of s + b) or SOFTMAX_SELECTED (the selection is the top-k of
    the logits, the weights their softmax over the k selected; no b).
    `balanced` [N, experts]: `_balanced_scores`, which then decide the
    selection, or None for the model's own."""
    with jax.named_scope("glm.moe.router"):
        logits = jnp.dot(x.astype(jnp.float32), p["gate"],
                         precision=jax.lax.Precision.HIGHEST)
        if scoring == SIGMOID:
            s = jax.nn.sigmoid(logits)
            select = balanced
            if select is None:
                select = s + jax.lax.stop_gradient(
                    p["e_score_correction_bias"])
        else:
            select = logits if balanced is None else balanced
        _, ids = jax.lax.top_k(select, share.top_k)
        ids = checkpoint_name(ids, SELECTION)
        if scoring == SIGMOID:
            w = jnp.take_along_axis(s, ids, axis=-1)
            if share.norm_topk:
                w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
        else:
            w = jax.nn.softmax(jnp.take_along_axis(logits, ids, axis=-1),
                               axis=-1)
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


def _inverse(order: jax.Array) -> jax.Array:
    return jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))


def _experts(gathered: jax.Array, e: dict, rows: jax.Array, dt, act
             ) -> jax.Array:
    with jax.named_scope("glm.moe.experts"):
        gate = jax.lax.ragged_dot(gathered, e["gate_proj"].astype(dt),
                                  rows)
        up = jax.lax.ragged_dot(gathered, e["up_proj"].astype(dt), rows)
        return jax.lax.ragged_dot(act(gate) * up,
                                  e["down_proj"].astype(dt), rows)


def _full_width(flat, e, w, order, inverse, rows, dt, act) -> jax.Array:
    """The routed experts' sum over [k N, hidden] buffers: flat
    [N, hidden], w [N, k], order/inverse [k N], rows [held] ->
    [N, hidden]."""
    with jax.named_scope("glm.moe.dispatch"):
        live = jnp.arange(order.shape[0]) < rows.sum()
        # masked both ways: a row past the last group reads zeros,
        # and whatever the grouped matmul's transpose leaves in its
        # cotangent never reaches the token it was gathered from
        gathered = jnp.where(live[:, None],
                             _dispatch(flat, order, inverse), 0)
        w_sorted = jnp.where(live, w.reshape(-1)[order], 0.0)
    y = _experts(gathered, e, rows, dt, act)
    with jax.named_scope("glm.moe.dispatch"):
        # rows past the last group are whatever the kernel left
        y = jnp.where(live[:, None], y, 0) * w_sorted[:, None].astype(dt)
        return _combine(y, order, inverse, flat.shape[0])


def _compact(c: int, dt, act, flat, e, w, order, inverse, rows
             ) -> jax.Array:
    """The same sum over [c, hidden] buffers, for a step whose
    `rows.sum() <= c`: the live rows are the first of the sorted
    order."""
    with jax.named_scope("glm.moe.dispatch"):
        head = order[:c]
        token = head // w.shape[1]
        live = jnp.arange(c) < rows.sum()
        # an assignment sorted past the buffer has no row in it; one
        # sorted past the last group has a row of zeros, masked below
        back = jnp.minimum(inverse, c)
        gathered = jnp.where(live[:, None],
                             _dispatch_some(flat, token, back), 0)
        w_sorted = jnp.where(live, w.reshape(-1)[head], 0.0)
    y = _experts(gathered, e, rows, dt, act)
    with jax.named_scope("glm.moe.dispatch"):
        y = jnp.where(live[:, None], y, 0) * w_sorted[:, None].astype(dt)
        return _combine_some(y, token, back, flat.shape[0])


def _transposed(path, dt, g, flat, e, w, order, inverse, rows):
    """Cotangents of (flat, the matrices once in `dt`, w)."""
    return jax.vjp(lambda *primals: path(*primals, order, inverse, rows),
                   flat, jax.tree.map(lambda m: m.astype(dt), e), w)[1](g)


# The branch a fitting step never takes is traced once for each shape,
# not once for each layer and net application (`jax.jit`: calls of one
# function): tracing and lowering Trinity-Mini's `train_many` took
# 9.0 s on the host without this, 7.1 with it and 6.75 before the
# branch existed, and set-up is a metric. The price: in a step that
# takes it, its ops carry the name stack of the call that traced them.
@partial(jax.jit, static_argnums=(0, 1))
def _overflow(dt, act, flat, e, w, order, inverse, rows):
    return _full_width(flat, e, w, order, inverse, rows, dt, act)


@partial(jax.jit, static_argnums=(0, 1))
def _overflow_transposed(dt, act, g, flat, e, w, order, inverse, rows):
    return _transposed(partial(_overflow, dt, act), dt, g, flat, e, w,
                       order, inverse, rows)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _routed(c: int, dt, act, flat, e, w, order, inverse, rows
            ) -> jax.Array:
    """The routed experts' sum [N, hidden] through buffers of c rows
    when the step's rows fit them, of k N rows when they do not. `e`:
    the parameters as they are stored; the cast to `dt` is each
    branch's own (`_experts`), so that no copy of them is an operand."""
    return jax.lax.cond(fits(rows, c), partial(_compact, c, dt, act),
                        partial(_overflow, dt, act),
                        flat, e, w, order, inverse, rows)


def _routed_fwd(c, dt, act, flat, e, w, order, inverse, rows):
    return (_routed(c, dt, act, flat, e, w, order, inverse, rows),
            (flat, e, w, order, inverse, rows))


def _routed_bwd(c, dt, act, res, g):
    rows = res[-1]
    d_flat, d_e, d_w = jax.lax.optimization_barrier(jax.lax.cond(
        fits(rows, c),
        partial(_transposed, partial(_compact, c, dt, act), dt),
        partial(_overflow_transposed, dt, act), g, *res))
    # A conditional's results are held apart from the arrays XLA:TPU
    # packs into one heap for as long as they live, and the matrices'
    # gradients live until the optimizer reads them: compiled for a
    # v5e they cost GLM-4.7-Flash's share 0.47 GiB and Trinity-Mini's
    # 0.44 (PERF.md section 6, PR 33). So they are copied out at once,
    # by a select the compiler cannot see through, and stay in `dt`
    # until then (without the barriers the select and the cast to
    # float32 move into the branches: twice the bytes, held as long).
    d_e = jax.lax.optimization_barrier(jax.tree.map(
        lambda d: jnp.where(rows.sum() >= 0, d, 0), d_e))
    return (d_flat, jax.tree.map(lambda d, m: d.astype(m.dtype), d_e,
                                 res[1]), d_w, None, None, None)


_routed.defvjp(_routed_fwd, _routed_bwd)


class Plan(NamedTuple):
    """Where each of a step's k N assignments goes: everything the
    layer decides before a row is fetched. It is made from the tensor
    the router reads, which need not be the tensor the experts are fed
    (`plan`)."""
    ids: jax.Array       # [N, k] int32, the selection
    w: jax.Array         # [N, k] float32, its weights
    order: jax.Array     # [k N] int32: the assignments sorted by local
    inverse: jax.Array   # expert (not-held ones last), and back
    rows: jax.Array      # [held] int32 routed to each held expert


def plan(p: dict, x: jax.Array, share: ExpertShare, balanced=None,
         scoring: str = SIGMOID) -> Plan:
    """x [N, hidden], the tensor the router reads -> the step's Plan:
    `route`, then the sort by held expert, its inverse and the counts.
    `p`: `gate` [hidden, experts] and, for SIGMOID,
    `e_score_correction_bias` [experts]; `balanced` [N, experts]."""
    ids, w = route(p, x, share, balanced, scoring)
    held = share.held
    with jax.named_scope("glm.moe.dispatch"):
        local = ids.reshape(-1) - share.first                # [k N]
        here = (local >= 0) & (local < held)
        slot = jnp.where(here, local, held)    # not held: sorts last
        order = jnp.argsort(slot, stable=True).astype(jnp.int32)
        inverse = _inverse(order)
        rows = jnp.bincount(slot, length=held + 1)[:held].astype(
            jnp.int32)
    return Plan(ids, w, order, inverse, rows)


def expert_ffn(p: dict, x: jax.Array, dt, share: ExpertShare,
               balanced=None, *, planned: Plan | None = None,
               act=jax.nn.silu):
    """x [B, T, hidden] -> (FFN(x), rows routed to each held expert
    [held] int32, the top-k ids [B, T, k]). `p`: `experts` (the held
    ones' three matrices stacked on a leading axis), `shared_experts`
    if the model has one, and what `plan` reads. `planned`: the Plan,
    where the caller made it already from another tensor than x; else
    it is made here from x, `balanced` [B, T, experts] deciding the
    selection as in `route`. `act`: the experts' gate activation."""
    b, t, h = x.shape
    n, k = b * t, share.top_k
    flat = x.reshape(n, h)
    if planned is None:
        planned = plan(p, flat, share,
                       None if balanced is None else balanced.reshape(n, -1))
    ids, w, order, inverse, rows = planned
    c = capacity(share, n)
    if c == n * k:
        out = _full_width(flat, p["experts"], w, order, inverse, rows,
                          dt, act)
    else:
        out = _routed(c, dt, act, flat, p["experts"], w, order, inverse,
                      rows)
    if "shared_experts" in p:
        with jax.named_scope("glm.moe.shared"):
            out = out + _swiglu(flat, p["shared_experts"], dt)
    return out.reshape(b, t, h), rows, ids.reshape(b, t, k)
