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


@jax.named_scope(UPDATE_SCOPE)
def update(tree: jax.Array, leaf_idx: jax.Array,
           priorities: jax.Array) -> jax.Array:
    """Set priorities at leaf_idx ([B] int32) and repair ancestor sums."""
    cap = capacity_of(tree)
    depth = cap.bit_length() - 1  # log2(cap)
    node = leaf_idx.astype(jnp.int32) + cap
    tree = tree.at[node].set(priorities.astype(jnp.float32))
    for _ in range(depth):
        node = node >> 1
        child_sum = tree[2 * node] + tree[2 * node + 1]
        tree = tree.at[node].set(child_sum)
    return tree


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
