"""Device-side sum-tree (segment tree) for prioritized replay.

The reference keeps its sum-tree on the host (SURVEY.md §2.2 "Prioritized
replay", §2.3 item 5); here it is a single `(2*capacity,)` float32 array
in HBM, with batched updates and stratified sampling running *inside* the
learner jit (BASELINE.json north_star: "the prioritized-replay sum-tree
and importance-sampling weights live in HBM with device-side sampling").

Layout: 1-indexed implicit binary tree. tree[1] is the root (total
priority), leaves live at tree[capacity + i] for i in [0, capacity).
Capacity must be a power of two so the descent depth is static.

TPU-first design notes:
- Updates recompute parents bottom-up: scatter leaf values, then per
  level gather both children and scatter their sum. Recomputation (not
  delta-accumulation) makes duplicate indices in one batch harmless,
  so no host-side dedup is ever needed.
- The dense top (`update`, `dense_levels`; ISSUE 36). An indexed level
  is two gathers and a scatter of ALL n indices, each waiting on the
  one before, whatever the level's width: level 5 has 32 nodes and a
  batch of 2,048 walked it 2,048 times over. The layout puts levels
  0 .. L-1 in one contiguous prefix, tree[1 : 2^L], so `update` walks
  by index only down to level L and rebuilds the prefix from level L as
  L rounds of adjacent-pair sums, written back with one
  dynamic_update_slice. Every parent is still fl(left + right) and
  `update` is the tree's only writer, so untouched nodes are rewritten
  with the value they had: the tree is the all-indexed walk's bit for
  bit (tests/test_sum_tree_dense_top.py keeps that walk; on the chip
  every row of the table below compared equal to L = 0 as 32-bit words).
- How the pair sum is written decides everything. Compiled for a
  described v5e, then timed on one (my chip runs, PR 36: 64 updates a
  program, random leaves with duplicates; 2^20 tree, n = 2,048, us a
  call; the all-indexed walk is 930):
  * `x[0::2] + x[1::2]` gathers again: 478 at L = 12, 758 at L = 15;
  * `x.reshape(-1, 2).sum(-1)` pads a minor dimension of 2 to the
    lanes: 1,246 at L = 12, temp 0.5 GiB, and not fl(left + right) on
    every backend;
  * `lax.reduce_window(x, 0., add, (2,), (2,))` over the flat level:
    L `reduce-window` ops with their intermediates in VMEM (temp
    126 kB), but XLA:TPU adds ONE NODE A CYCLE (1.06 ns): 323 at
    L = 16, the best, and 1,273 at L = 20;
  * the same window over a [rows, 128] view, `(1, 2)` with stride
    `(1, 2)`, is vectorised: 254 at L = 16, 171 at 18, **96 at L = 20**
    (`_pair_sums`; a transpose with a sublane-strided add reads 108
    and a lane rotate with a strided slice 118).
- What a split costs (same probe, us a call at L = 0 / the best L):
  (2^20, 2,048) 930 / 96 at 20; (2^20, 512) 1,067 / 87 at 20;
  (2^20, 64) 611 / 84 at 20; (2^20, 16) 570 / 85 at 20 (86 at 19);
  (2^14, 256) 81 / 19 at 14; (2^16, 16) 35 / 22 at 14-16; (4,096, 2)
  22 / 14 at 12: every level dense wins or ties wherever measured,
  and the time falls level by level all the way. The one tie places
  the constant: 16 indices walk level 19 in the time its 2^19 nodes
  are re-added, 32,768 nodes an index.
- Sampling is a vectorized prefix-sum descent: log2(capacity) rounds
  of "read every draw's left child, go right where u >= left", each
  waiting on the one before; no data-dependent control flow, unrolled
  (static trip count). Read by index a round is a gather of n random
  rows whatever the level's width: 14.5 us for 2,048 draws, and level 5
  has 32 nodes (ISSUE 51; `sample`, `dense_descent_levels`). At level l
  the left children are the even entries of tree[2^(l+1) : 2^(l+2)], a
  static slice of the contiguous prefix, so down to level L-1 the read
  is a SELECT over the level's own left children: compare the draw's
  position in the level against a row of 2^l positions, keep the one
  that matches, sum. One term is not zero, so `left` is the gather's
  float32 and u, idx, the leaf, the clamp and probs are the
  all-indexed descent's bit for bit (tests/test_sum_tree_dense_top.py
  keeps that descent; on the chip every row below compared equal to
  L = 0 as 32-bit words, leaves and probs). Below level L-1 the walk
  is `tree[2 * idx]` as it was.
- What the descent costs by form and L (my chip runs, PR 51: one v5e,
  64 descents a program with the tree changing between them, us a call,
  the least of 7; 2^20 tree, 2,048 draws in 4 chunks; L = 0 is 333.8):
  * where the even entries come from decides the high end. One strided
    slice `tree[:2^(L+1):2]` a call "gathers again", as the update's
    did: 204 at L = 9, 190 at 11, 192 at 12, 210 at 13 (a slice per
    level: 188 / 194 / 214). The update's own pair sums over the prefix
    with its right children zeroed (`_left_children`: x + 0.0 is x) are
    vectorised and cost nothing that shows: 203 / 177 / 165 / **155**
    at L = 9 / 11 / 12 / 13, 152 at 14, 160 at 15, 194 at 16, 269 at
    17. `reshape(-1, 2)[:, 0]` ties to L = 12 and pays for its padded
    minor dimension after (161 at 13, 167 at 14, 389 at 17);
  * which operand becomes the column (XLA relayouts it) decides the
    rest. Draws in the lanes, nodes down the sublanes (the level is
    the column): 155.5 / 154.5 / 159 at L = 13 / 14 / 15, but 62.6 /
    69.5 / 85 for 512 draws and 36 / 44 for 256. Nodes in the lanes
    (the draws are the column): 158 / 160 / 172, and 58.0 / 58.9 / 61
    for 512, 32 / 29 for 256. The SHORTER operand as the column
    (`_select`): 155.0 / 153.8 / 168, 56.6 / 57.9 / 61.5, 31.0 / 29.8 -
    the best or a tie in every cell of the table; the longer one as
    the column is the worst in every cell;
  * a one-hot [n, 2^l] bfloat16 times the level as three bfloat16
    pieces (exact: they hold a float32), or times the float32 level at
    `Precision.HIGHEST`, from 256 nodes up: 159 / 161 / 177 at L = 13 /
    14 / 15, never ahead of the select, and an inf in the tree would
    reach every draw as a NaN.
- Where the constant was placed: a level costs n * 2^l lane-operations
  dense and n row fetches indexed, so the crossover in l hardly
  depends on n, and it is the one tie again. In the probe, (2^20,
  2,048): level 12 (4,096 nodes) still saves 10 us, level 13 saves 1.2,
  level 14 costs 14; (2^20, 512), L = 0 / 12 / 13 / 14 / 15: 150.9 /
  59.2 / 56.6 / 57.9 / 61.5; (2^14, 256): 46.2 / 30.5 / 31.0 / 29.8 (=
  every level); (2^16, 128) and (4,096, 128): 35.8 -> 28.5 and 31.0 ->
  25.3, flat from L = 10. Level 13 is the tie, and the cell broke it
  (`pong_offline`, samples/s on one seed, L = 13 / 14 / 15 / 16:
  515,456 / **516,639** / 515,552 / 510,753 against the indexed walk's
  493,821, where two runs of one tree differ by 8; traced, a gather is
  14.71 us, the selects of levels 10 / 11 / 12 / 13 are 1.30 / 3.64 /
  6.05 / 13.15, and the fusion that ends the walk gave back 7.6 more
  when level 13's gather left its side): 8,192 nodes a draw, fourteen
  levels. Under a row of lanes of draws nothing wins: (2^16, 16) reads
  25 at L = 0, 27-29 up to L = 9 and 86 at 13, (2^11, 2) 23 and 24-26;
  those call sites (the decoder family's, 1-16 sequences a step) keep
  the indexed walk and their lowered programs to the byte.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def init(capacity: int) -> jax.Array:
    assert capacity > 0 and (capacity & (capacity - 1)) == 0, \
        "capacity must be a power of two"
    return jnp.zeros(2 * capacity, jnp.float32)


def capacity_of(tree: jax.Array) -> int:
    return tree.shape[0] // 2


def total(tree: jax.Array) -> jax.Array:
    return tree[1]


def leaves(tree: jax.Array) -> jax.Array:
    return tree[capacity_of(tree):]


# the tree's two passes as `jax.named_scope`s (op metadata only). Both
# nest in runtime/learner.py::CYCLE_SCOPES inside a learner's cycle:
# the descent under `cycle.sample`, the learner's update under
# `cycle.write_back`; an update under no `cycle.*` name is an ingest add
DESCENT_SCOPE, UPDATE_SCOPE = "sum_tree.descent", "sum_tree.update"


# How many nodes of a level the dense pass re-adds in the time `update`
# takes to walk ONE index up one level (two gathers and a scatter).
# Measured, not derived (one v5e, my chip runs, PR 36; the module
# docstring has the table): on a 2^20 tree 16 indices walk level 19 in
# the time its 2^19 nodes are re-added (85.7 us a call at L = 19, 84.9
# at L = 20), and at every wider batch and smaller tree measured the
# dense level won outright. An index costs most where a batch is
# smallest (an op's fixed cost), so this is the crossover's high end:
# past it a huge tree's small batch keeps its widest levels indexed.
DENSE_NODES_PER_INDEX = 1 << 15


def dense_levels(capacity: int, n: int) -> int:
    """How many levels from the root down `update` rebuilds densely for
    a batch of n indices: levels 0 .. L-1, the contiguous prefix
    tree[1 : 2^L]. Level l is dense when re-adding its 2^l nodes costs
    less than walking n indices through it, 2^l <= n *
    DENSE_NODES_PER_INDEX; the levels above a dense one are narrower, so
    dense too. From the shapes alone, at trace time: every call site
    gets its own split. n >= capacity makes every level dense."""
    depth = capacity.bit_length() - 1
    return min(depth, (n * DENSE_NODES_PER_INDEX).bit_length())


LANES = 128  # a TPU vector register's minor dimension


# How many nodes of a level the descent selects among, for every draw,
# in the time it takes to fetch ONE draw's left child by index. Measured,
# not derived (one v5e, my chip runs, PR 51; the module docstring has
# the table: the probe's tie at level 13, which `pong_offline` broke).
# A select costs n * 2^l lane-operations and a gather n row fetches, so
# the crossover hardly depends on n.
DENSE_NODES_PER_DRAW = 1 << 13


def dense_descent_levels(capacity: int, n: int) -> int:
    """How many levels from the root down `sample` reads densely for n
    draws: levels 0 .. L-1, whose left children are the even entries of
    the contiguous prefix tree[: 2^(L+1)]. Level l is dense when it has
    no more than DENSE_NODES_PER_DRAW nodes; under a row of lanes of
    draws an op costs its launch either way and the walk stays indexed.
    From the shapes alone, at trace time, as `dense_levels`."""
    if n < LANES:
        return 0
    depth = capacity.bit_length() - 1
    return min(depth, DENSE_NODES_PER_DRAW.bit_length())


def _pair_sums(level: jax.Array) -> jax.Array:
    """[2m] -> [m], out[i] = level[2i] + level[2i+1], as a window
    reduction (a strided slice gathers again and a [m, 2] view pads its
    minor dimension to the lanes: module docstring). From a row of
    lanes up the window runs over a [rows, LANES] view: over the flat
    array XLA:TPU adds one node a cycle."""
    if level.shape[0] < LANES:
        return jax.lax.reduce_window(level, 0.0, jax.lax.add, (2,), (2,),
                                     "VALID")
    return jax.lax.reduce_window(
        level.reshape(-1, LANES), 0.0, jax.lax.add, (1, 2), (1, 2),
        "VALID").reshape(-1)


@jax.named_scope(UPDATE_SCOPE)
def update(tree: jax.Array, leaf_idx: jax.Array,
           priorities: jax.Array) -> jax.Array:
    """Set priorities at leaf_idx ([B] int32) and repair ancestor sums:
    by index through the levels wider than the batch, then the prefix
    above them in one dense pass (`dense_levels`). Every parent is
    fl(left + right) of its children either way, so the tree is the
    all-indexed walk's bit for bit."""
    cap = capacity_of(tree)
    depth = cap.bit_length() - 1  # log2(cap)
    dense = dense_levels(cap, leaf_idx.shape[0])
    node = leaf_idx.astype(jnp.int32) + cap
    tree = tree.at[node].set(priorities.astype(jnp.float32))
    for _ in range(depth - dense):
        node = node >> 1
        child_sum = tree[2 * node] + tree[2 * node + 1]
        tree = tree.at[node].set(child_sum)
    if not dense:
        return tree
    # level `dense` is whole and current; every level above it is its
    # pairwise sums, and levels 0 .. dense-1 are tree[1 : 2^dense].
    # Untouched nodes get the value they had: update is the only writer
    level = tree[1 << dense:2 << dense]
    prefix = []
    for _ in range(dense):
        level = _pair_sums(level)
        prefix.append(level)
    prefix.append(jnp.zeros(1, jnp.float32))  # node 0 is no node
    return jax.lax.dynamic_update_slice(
        tree, jnp.concatenate(prefix[::-1]), (0,))


def chunk_major(x: jax.Array, chunks: int) -> jax.Array:
    """Reorder the leading axis of a K*B draw from stratum order to
    CHUNK-MAJOR order: position p = j*B + i takes element i*K + j, so
    the contiguous block [j*B, (j+1)*B) of anything gathered in this
    order is chunk j: the INTERLEAVED strata {j, j+K, j+2K, ...}.

    Why interleaved: stratum s of a K*B descent covers cumulative-mass
    slice [s, s+1)/(K*B) over leaves in ring-insertion order, so a
    chunk of CONTIGUOUS strata would be one age-correlated 1/K slice
    of the replay (oldest quarter, ..., newest quarter); each chunk
    must span the full priority range. Why here, on the draw: the
    permutation depends on the position alone, so applied to the K*B
    scalars before the storage gather it moves a few kB, where applied
    to the gathered batch it rewrites every sampled frame once more.
    This is the ONE place the permutation lives — every storage layout
    (and the uniform replay's indices) goes through it."""
    if chunks <= 1:
        return x
    return x.reshape(x.shape[0] // chunks, chunks,
                     *x.shape[1:]).swapaxes(0, 1).reshape(x.shape)


def _left_children(tree: jax.Array, dense: int) -> jax.Array:
    """-> [2^dense], out[k] = tree[2k]: the left children of levels
    0 .. dense-1, level l's at out[2^l : 2^(l+1)]. The update's pair
    sums over the prefix with its right children zeroed: x + 0.0 is x,
    and a strided slice gathers (module docstring)."""
    top = tree[:2 << dense]
    is_left = (jnp.arange(2 << dense, dtype=jnp.int32) & 1) == 0
    return _pair_sums(jnp.where(is_left, top, 0.0))


def _select(level: jax.Array, pos: jax.Array) -> jax.Array:
    """level[pos] ([n] from [m]) as a compare, a select and a sum over
    the level's own nodes: one term of a sum is not zero, so the sum is
    that term, exactly. The shorter of the two operands becomes the
    column (its relayout is the form's own cost: module docstring)."""
    nodes = jnp.arange(level.shape[0], dtype=jnp.int32)
    if level.shape[0] < pos.shape[0]:
        return jnp.sum(jnp.where(nodes[:, None] == pos[None, :],
                                 level[:, None], 0.0), axis=0)
    return jnp.sum(jnp.where(pos[:, None] == nodes[None, :],
                             level[None, :], 0.0), axis=1)


@jax.named_scope(DESCENT_SCOPE)
def sample(tree: jax.Array, rng: jax.Array, batch: int,
           size: jax.Array | None = None,
           chunks: int = 1) -> tuple[jax.Array, jax.Array]:
    """Stratified proportional sampling.

    Returns (leaf_idx [batch] int32, probs [batch] f32) where probs are
    normalized leaf probabilities p_i / total. Stratification: the draw
    for stratum s is uniform in the s-th of `batch` equal slices of the
    total mass (variance reduction, as in standard PER implementations).

    Order of the draw: with chunks=1 position s holds stratum s. With
    chunks=K (the learners' K-batch cycle, batch = K*B) the stratified
    u is laid out chunk-major BEFORE the descent (`chunk_major`):
    position j*B + i holds stratum i*K + j. The descent is elementwise,
    so that leaf is bit for bit the one a stratum-order draw followed
    by a `reshape(B, K).swapaxes(0, 1)` puts at chunk j, slot i — the
    same leaves from the same rng, emitted in the order the K SGD
    steps consume them.

    `size` (int32, number of live leaves) clamps the descent's landing
    spot into the filled region: float32 rounding in the stratified u or
    the accumulated left-child sums can walk the descent one leaf past
    the live mass onto a zero-priority slot, and an all-zero tree would
    deterministically return the rightmost leaf. Probs are re-gathered
    after clamping so IS weights always describe the leaf actually
    returned.

    The top `dense_descent_levels` levels read a draw's left child as a
    select over the level's own nodes, the levels below by index: the
    same float32 either way, so the draw is the all-indexed descent's
    bit for bit (module docstring).
    """
    cap = capacity_of(tree)
    depth = cap.bit_length() - 1
    tot = tree[1]
    u = (jnp.arange(batch, dtype=jnp.float32)
         + jax.random.uniform(rng, (batch,))) / batch * tot
    u = chunk_major(u, chunks)
    idx = jnp.ones(batch, jnp.int32)
    dense = dense_descent_levels(cap, batch)
    if dense:
        lefts = _left_children(tree, dense)
    for level in range(depth):
        if level < dense:
            left = _select(lefts[1 << level:2 << level], idx - (1 << level))
        else:
            left = tree[2 * idx]
        go_right = u >= left
        u = jnp.where(go_right, u - left, u)
        idx = 2 * idx + go_right.astype(jnp.int32)
    leaf = idx - cap
    if size is not None:
        leaf = jnp.minimum(leaf, jnp.maximum(size, 1) - 1)
    probs = tree[cap + leaf] / jnp.maximum(tot, 1e-12)
    return leaf, probs


@partial(jax.jit, static_argnums=(2,))
def sample_jit(tree, rng, batch):
    return sample(tree, rng, batch)


update_jit = jax.jit(update, donate_argnums=(0,))
