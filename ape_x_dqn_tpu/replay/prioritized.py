"""Device-resident prioritized replay.

Storage (an arbitrary transition pytree of per-slot arrays) and the
sum-tree both live in HBM; add / sample / priority-update are pure
functions designed to be fused into the learner's single jit (SURVEY.md
§7 step 5, §2.3 item 5). The learner is the single owner of the buffer
state, which removes the sample-vs-update race of host-side designs by
construction (SURVEY.md §5 "race detection").

Conventions (Schaul et al. 2016; Horgan et al. 2018):
- stored priority = (|td| + eps)^alpha  (alpha applied at write time)
- IS weight w_i = (N * P(i))^-beta, normalized by max over the batch
- new transitions arrive WITH priorities (actors compute initial
  priorities actor-side — SURVEY.md §2.2 "Actor runtime")
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ape_x_dqn_tpu.ops import sum_tree
from ape_x_dqn_tpu.replay.packing import (PixelPacker, dus_rows,
                                          dus_rows_per_shard, gather_rows,
                                          make_packer, ring_write_size,
                                          ring_write_start)


class ReplayState(NamedTuple):
    storage: Any          # pytree of [capacity, ...] arrays
    tree: jax.Array       # (2*capacity,) sum-tree of p^alpha
    pos: jax.Array        # int32 next write cursor
    size: jax.Array       # int32 filled slots


def ring_cursor(pos, size, block: int, capacity: int, nl: int,
                size_scale: int = 1):
    """Skip-to-head cursor math shared by every ring layout and both
    the single-chip (nl=0) and lockstep-dist (nl=1, [dp]-vector
    cursors) forms: -> (start, new_pos, new_size). `size_scale`
    converts cursor units to size units (the frame ring's cursor
    counts segments while its size counts transitions)."""
    pos0 = pos if nl == 0 else pos[0]
    size0 = size if nl == 0 else size[0]
    start = ring_write_start(pos0, block, capacity)
    pos1 = (start + block) % capacity
    size1 = ring_write_size(size0, start * size_scale,
                            block * size_scale, capacity * size_scale)
    return start, pos1, size1


def ring_finish(tree, idx, pri, pos1, size1, lead: tuple[int, ...]):
    """Tree write-back + cursor broadcast shared by every ring layout:
    single-chip (lead=()) updates the one tree; the lockstep-dist form
    (idx [b], same every shard) vmaps the small per-shard trees (the
    storage itself was already written with one multi-axis DUS) and
    broadcasts the common cursor to [dp] vectors; the DIRECTED dist
    form (idx [dp, b], each shard's evict_plan picked its own region)
    vmaps tree AND indices and passes the per-shard [dp] cursors
    through. -> (tree, pos, size)."""
    if not lead:
        return sum_tree.update(tree, idx, pri), pos1, size1
    if idx.ndim == 1:
        tree = jax.vmap(sum_tree.update,
                        in_axes=(0, None, 0))(tree, idx, pri)
        return (tree, jnp.full(lead, pos1, jnp.int32),
                jnp.full(lead, size1, jnp.int32))
    tree = jax.vmap(sum_tree.update, in_axes=(0, 0, 0))(tree, idx, pri)
    return tree, pos1.astype(jnp.int32), size1.astype(jnp.int32)


class PrioritizedReplay:
    """Static config + pure state-transition functions.

    Pixel leaves are stored as exactly-tiled rows of words and ring writes
    are in-place dynamic_update_slice blocks with skip-to-head wrap —
    see replay/packing.py for the measured HBM rationale (a scatter or
    a tile-padded layout each cost a full-buffer copy per add/sample on
    TPU).
    """

    def __init__(self, capacity: int, alpha: float = 0.6, beta: float = 0.4,
                 eps: float = 1e-6, item_spec: Any = None):
        assert capacity > 0 and (capacity & (capacity - 1)) == 0, \
            "capacity must be a power of two"
        self.capacity = capacity
        self.alpha = alpha
        self.beta = beta
        self.eps = eps
        self._packer: PixelPacker | None = None
        self._storage_spec: Any = None
        self._rows: Any = None   # storage rows per item, per leaf
        # packer construction is DETERMINISTIC: given a spec here, the
        # codec exists from construction — encode/decode behavior no
        # longer depends on whether init() happened to run first (the
        # hidden side effect a replay shared across restore paths could
        # otherwise observe mid-flight)
        if item_spec is not None:
            self._build_packer(item_spec)

    def _build_packer(self, item_spec: Any) -> None:
        self._packer, self._storage_spec, self._rows = make_packer(
            item_spec)

    # -- state construction ------------------------------------------------

    def init(self, item_spec: Any = None) -> ReplayState:
        """item_spec: pytree of ShapeDtypeStruct (or arrays) for ONE
        item. Optional when the constructor already received it; calling
        with neither raises instead of silently building packer-less
        storage."""
        if item_spec is not None:
            self._build_packer(item_spec)
        if self._storage_spec is None:
            raise ValueError(
                "PrioritizedReplay has no item spec — pass item_spec to "
                "the constructor or to init()")
        storage = jax.tree.map(
            lambda s, m: jnp.zeros((self.capacity * m, *s.shape), s.dtype),
            self._storage_spec, self._rows)
        return ReplayState(
            storage=storage, tree=sum_tree.init(self.capacity),
            pos=jnp.int32(0), size=jnp.int32(0))

    def checkpoint_rows(self, storage: Any, restore: bool = False) -> Any:
        """HOST storage <-> its form in a replay-bearing checkpoint:
        packed leaves as byte rows (PixelPacker.checkpoint_rows)."""
        if self._packer is None:
            return storage
        return self._packer.checkpoint_rows(storage, restore)

    # -- transitions (all pure, jit-friendly) ------------------------------

    def _write_block(self, state: ReplayState, items: Any,
                     td_abs: jax.Array, lead: tuple[int, ...],
                     start: jax.Array | None = None) -> ReplayState:
        """Shared body of `add` (lead=()) and `add_lockstep`
        (lead=(dp,)): one in-place dynamic_update_slice block per leaf
        with skip-to-head wrap; only the small per-shard sum-trees go
        through vmap on the lockstep path."""
        nl = len(lead)
        b = td_abs.shape[nl]
        per_shard = False
        if start is None:
            start, pos1, size1 = ring_cursor(state.pos, state.size, b,
                                             self.capacity, nl)
        else:
            # directed write (add_at / add_at_lockstep): overwrite the
            # caller-chosen region; the cursor resumes after it so
            # subsequent FIFO adds don't immediately clobber what was
            # just written. Dist form: start is a [dp] vector (each
            # shard's evict_plan picked its own region) and the cursor
            # math is elementwise over shards.
            per_shard = nl > 0
            pos1 = (start + b) % self.capacity
            size1 = ring_write_size(state.size, start, b, self.capacity)
        if per_shard:
            idx = start[:, None] + jnp.arange(b, dtype=jnp.int32)[None]
        else:
            idx = start + jnp.arange(b, dtype=jnp.int32)  # every shard
        if self._packer is not None:
            items = self._packer.encode(items)
        if per_shard:
            storage = jax.tree.map(
                lambda buf, x, m: dus_rows_per_shard(buf, x, start * m),
                state.storage, items, self._rows)
        else:
            storage = jax.tree.map(
                lambda buf, x, m: dus_rows(buf, x, start * m, lead=nl),
                state.storage, items, self._rows)
        pri = (td_abs + self.eps) ** self.alpha
        tree, pos, size = ring_finish(state.tree, idx, pri, pos1, size1,
                                      lead)
        return ReplayState(storage=storage, tree=tree, pos=pos, size=size)

    def add(self, state: ReplayState, items: Any,
            td_abs: jax.Array) -> ReplayState:
        """Append a batch of items with initial |TD| priorities.

        items: pytree of [B, ...] arrays; td_abs: [B] f32. Overwrites
        FIFO when full; a block that would cross the ring boundary is
        written at slot 0 instead (skip-to-head — identical to modular
        semantics whenever the block size divides the capacity, which
        every fixed-block ingest staging guarantees).
        """
        return self._write_block(state, items, td_abs, lead=())

    def add_lockstep(self, state: ReplayState, items: Any,
                     td_abs: jax.Array) -> ReplayState:
        """`add` for [dp, ...]-stacked shard states whose cursors
        advance in LOCKSTEP — the dist ingest contract (every add ships
        equal-size [dp, B] blocks, so all shard cursors are equal by
        induction from init).

        Why not jax.vmap(add): vmap's batching rule rewrites
        dynamic_update_slice into lax.scatter, and a scatter into a
        donated buffer materializes a full-buffer HLO temp copy
        (measured 19.1GB on a 9.47GB ring — replay/packing.py). The
        lockstep form writes all shards with ONE multi-axis DUS at
        (0, start, 0...) covering the full dp extent, which stays in
        place (verified: temp=0 at the atari57 per-shard scale).
        """
        return self._write_block(state, items, td_abs,
                                 lead=(td_abs.shape[0],))

    # -- tiered cold store hooks (replay/cold_store.py; single-chip) -------
    #
    # Three pure functions the driver composes into its eviction cycle
    # when ReplayConfig.cold_tier_capacity > 0: pick the ring's
    # lowest-priority-mass contiguous region (evict_plan), read it out
    # in STAGING layout (read_region, fetched to host and handed to
    # ColdStore.put), then overwrite exactly that region with the fresh
    # staged block (add_at). With the tier off none of these run and
    # `add` keeps its blind skip-to-head FIFO — bitwise-identical
    # default path, pinned by tests/test_cold_store.py.

    def evict_plan(self, state: ReplayState, block: int) -> jax.Array:
        """Start slot of the minimum-priority-mass contiguous
        `block`-slot window (windowed leaf-mass sum via cumsum; the
        argmin range [0, capacity-block] never wraps, so the start is
        always a legal dynamic-slice origin)."""
        leaves = state.tree[self.capacity:]
        c = jnp.concatenate([jnp.zeros(1, leaves.dtype),
                             jnp.cumsum(leaves)])
        return jnp.argmin(c[block:] - c[:-block]).astype(jnp.int32)

    def read_region(self, state: ReplayState, start: jax.Array,
                    block: int) -> tuple[Any, jax.Array]:
        """-> (items [block, ...] in staging layout, stored leaf
        priorities [block]) for the region about to be overwritten."""
        def region(buf, m):
            out = jax.lax.dynamic_slice_in_dim(buf, start * m, block * m)
            return out if m == 1 else out.reshape(block, m,
                                                  *out.shape[1:])

        items = jax.tree.map(region, state.storage, self._rows)
        if self._packer is not None:
            items = self._packer.decode(items)
        pri = jax.lax.dynamic_slice_in_dim(
            state.tree, self.capacity + start, block)
        return items, pri

    def add_at(self, state: ReplayState, items: Any, td_abs: jax.Array,
               start: jax.Array) -> ReplayState:
        """Directed `add`: overwrite the `B` slots at `start` (an
        evict_plan result) instead of the FIFO cursor position."""
        return self._write_block(state, items, td_abs, lead=(),
                                 start=start)

    def add_at_lockstep(self, state: ReplayState, items: Any,
                        td_abs: jax.Array,
                        start: jax.Array) -> ReplayState:
        """Directed `add_lockstep`: shard d of the [dp, ...]-stacked
        state gets items[d] at start[d] (each shard's own evict_plan
        result — the dp form of the cold tier's eviction swap). Writes
        are dp unrolled single-shard DUS calls (dus_rows_per_shard);
        shard cursors DIVERGE here, which is safe because the eviction
        swap only runs once the ring is full — every subsequent ship
        routes back through evict_plan/add_at, so the lockstep FIFO
        cursor is never consulted again (pinned by
        tests/test_ingest.py's dp=2 cold closure test)."""
        return self._write_block(state, items, td_abs,
                                 lead=(td_abs.shape[0],), start=start)

    def sample_items(self, state: ReplayState, rng: jax.Array, batch: int,
                     chunks: int = 1
                     ) -> tuple[Any, jax.Array, jax.Array]:
        """-> (item batch pytree, leaf indices [B], probs [B]) without IS
        weights — the dist learner computes those globally across shards
        (parallel/dist_learner.py), and FrameRingReplay shares the
        calling convention. `chunks`=K emits the draw chunk-major
        (ops/sum_tree.py::sample), so the gather writes the batch in
        the order the K-batch cycle reads it, and each chunk's rows are
        gathered on their own, as FrameRingReplay._gather gathers them:
        chunk j of the result is then a whole array and not a slice of
        a K*B one, and the K SGD steps read it as it lands
        (r2d2_offline: 6.30 -> 5.99 ms a step, PERF.md §6, PR 42). The
        items returned are the same for every `chunks`."""
        idx, probs = sum_tree.sample(state.tree, rng, batch,
                                     size=state.size, chunks=chunks)
        # (a one-way split would still be a slice in the K = 1 programs)
        parts = jnp.split(idx, chunks) if chunks > 1 else [idx]
        # scope = op metadata: the benchmark reads the gather's device
        # time by this name (replay.seq_gather_hbm_share)
        with jax.named_scope("replay.sample_gather"):
            items = jax.tree.map(
                lambda buf, m: jnp.concatenate(
                    [gather_rows(buf, part, m) for part in parts]),
                state.storage, self._rows)
        if self._packer is not None:
            items = self._packer.decode(items, words=True)
        return items, idx, probs

    def sample(self, state: ReplayState, rng: jax.Array, batch: int,
               chunks: int = 1) -> tuple[Any, jax.Array, jax.Array]:
        """-> (item batch pytree, leaf indices [B], IS weights [B]), in
        stratum order, or chunk-major with `chunks`=K (sample_items).

        valid_mask zeroes the weight of storage layouts' dead slots
        BEFORE max-normalization (a ~zero-probability dead draw would
        otherwise become the max and crush every live weight); for flat
        storage it is all-ones and folds away."""
        items, idx, probs = self.sample_items(state, rng, batch, chunks)
        n = jnp.maximum(state.size.astype(jnp.float32), 1.0)
        w = (n * jnp.maximum(probs, 1e-12)) ** (-self.beta)
        w = w * self.valid_mask(state, idx)
        w = w / jnp.maximum(w.max(), 1e-12)
        return items, idx, w

    def update_priorities(self, state: ReplayState, idx: jax.Array,
                          td_abs: jax.Array) -> ReplayState:
        pri = (td_abs + self.eps) ** self.alpha
        return state._replace(tree=sum_tree.update(state.tree, idx, pri))

    def valid_mask(self, state: ReplayState, idx: jax.Array) -> jax.Array:
        """[B] f32: 1 where idx is trainable. Flat storage has no dead
        slots; the frame-ring layout overrides this (pad slots)."""
        return jnp.ones(idx.shape, jnp.float32)

    # -- learning-health accessors (obs/learning.py; pure, jit-safe) -------

    # static capability flag: UniformReplayDevice sets False so the
    # learner's diag tap specializes away the priority statistics
    has_priorities = True

    def leaf_priorities(self, state: ReplayState,
                        idx: jax.Array) -> jax.Array:
        """Stored p^alpha at the given leaf indices (any idx shape)."""
        return state.tree[self.capacity + idx]

    def cursor_transitions(self, state: ReplayState) -> jax.Array:
        """Write cursor in TRANSITION (= leaf-index) units, so ring
        distance to a sampled leaf is its age in transitions. The
        frame-ring layout overrides (its cursor counts segments)."""
        return state.pos

    # -- split entry points (double-buffered learner pipeline) -------------

    def sample_state(self, state: ReplayState, rng: jax.Array, batch: int,
                     chunks: int = 1
                     ) -> tuple[Any, jax.Array, jax.Array]:
        """SAMPLE half of the split learner cycle — `sample` under its
        pipeline-contract name. Reads only storage, tree, and size
        (never the write cursor `pos`), so a prefetched draw commutes
        with a concurrent `update_state` write-back: the draw simply
        sees the pre-write-back priorities, the one-dispatch staleness
        the double-buffered train_many accepts by design. Subclasses
        override sample/sample_items, not this delegator, so every
        storage layout inherits the contract."""
        return self.sample(state, rng, batch, chunks)

    def update_state(self, state: ReplayState, idx: jax.Array,
                     td_abs: jax.Array) -> ReplayState:
        """UPDATE half of the split learner cycle — `update_priorities`
        under its pipeline-contract name. Writes ONLY the sum-tree
        (storage/pos/size pass through untouched), which is what makes
        it safe to reorder against a prefetched sample_state draw."""
        return self.update_priorities(state, idx, td_abs)

    # -- convenience jitted endpoints (standalone use / replay server) -----

    @partial(jax.jit, static_argnums=0, donate_argnums=1)
    def add_jit(self, state, items, td_abs):
        return self.add(state, items, td_abs)

    @partial(jax.jit, static_argnums=(0, 3))
    def sample_jit(self, state, rng, batch):
        return self.sample(state, rng, batch)

    @partial(jax.jit, static_argnums=0, donate_argnums=1)
    def update_priorities_jit(self, state, idx, td_abs):
        return self.update_priorities(state, idx, td_abs)


class UniformReplayDevice:
    """Uniform ring buffer with the same pure-functional API (config 1).

    Sampling is uniform over filled slots; IS weights are all ones.
    """

    def __init__(self, capacity: int, item_spec: Any = None):
        assert capacity > 0 and (capacity & (capacity - 1)) == 0
        self.capacity = capacity
        self._packer: PixelPacker | None = None
        self._storage_spec: Any = None
        self._rows: Any = None   # storage rows per item, per leaf
        if item_spec is not None:  # deterministic, like PrioritizedReplay
            self._build_packer(item_spec)

    def _build_packer(self, item_spec: Any) -> None:
        self._packer, self._storage_spec, self._rows = make_packer(
            item_spec)

    def init(self, item_spec: Any = None) -> ReplayState:
        if item_spec is not None:
            self._build_packer(item_spec)
        if self._storage_spec is None:
            raise ValueError(
                "UniformReplayDevice has no item spec — pass item_spec "
                "to the constructor or to init()")
        storage = jax.tree.map(
            lambda s, m: jnp.zeros((self.capacity * m, *s.shape), s.dtype),
            self._storage_spec, self._rows)
        return ReplayState(storage=storage,
                           tree=jnp.zeros(1, jnp.float32),  # unused
                           pos=jnp.int32(0), size=jnp.int32(0))

    checkpoint_rows = PrioritizedReplay.checkpoint_rows

    def add(self, state: ReplayState, items: Any,
            td_abs: jax.Array | None = None) -> ReplayState:
        b = jax.tree.leaves(items)[0].shape[0]
        start = ring_write_start(state.pos, b, self.capacity)
        if self._packer is not None:
            items = self._packer.encode(items)
        storage = jax.tree.map(
            lambda buf, x, m: dus_rows(buf, x, start * m),
            state.storage, items, self._rows)
        return ReplayState(
            storage=storage, tree=state.tree,
            pos=(start + b) % self.capacity,
            size=ring_write_size(state.size, start, b, self.capacity))

    def sample(self, state: ReplayState, rng: jax.Array, batch: int,
               chunks: int = 1):
        # no strata here, but the K-batch cycle's chunk membership is a
        # function of the position in the draw all the same: permute
        # the indices, not the gathered items (sum_tree.chunk_major)
        idx = sum_tree.chunk_major(
            jax.random.randint(rng, (batch,), 0,
                               jnp.maximum(state.size, 1)), chunks)
        items = jax.tree.map(lambda buf, m: gather_rows(buf, idx, m),
                             state.storage, self._rows)
        if self._packer is not None:
            items = self._packer.decode(items, words=True)
        return items, idx, jnp.ones(batch, jnp.float32)

    def update_priorities(self, state: ReplayState, idx, td_abs):
        return state

    # learning-health accessors: no tree, so priority statistics are
    # statically skipped by the learner's diag tap
    has_priorities = False

    def leaf_priorities(self, state: ReplayState, idx):
        return jnp.zeros(idx.shape, jnp.float32)

    def cursor_transitions(self, state: ReplayState):
        return state.pos

    # split entry points (see PrioritizedReplay): sampling is uniform
    # and updates are no-ops, so the commuting contract holds trivially
    def sample_state(self, state: ReplayState, rng: jax.Array, batch: int,
                     chunks: int = 1):
        return self.sample(state, rng, batch, chunks)

    def update_state(self, state: ReplayState, idx, td_abs):
        return self.update_priorities(state, idx, td_abs)
