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
- Sampling is a vectorized prefix-sum descent: log2(capacity) iterations
  of a batched gather — no data-dependent control flow, fully unrolled
  by XLA (static trip count).
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
    """
    cap = capacity_of(tree)
    depth = cap.bit_length() - 1
    tot = tree[1]
    u = (jnp.arange(batch, dtype=jnp.float32)
         + jax.random.uniform(rng, (batch,))) / batch * tot
    u = chunk_major(u, chunks)
    idx = jnp.ones(batch, jnp.int32)
    for _ in range(depth):
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
