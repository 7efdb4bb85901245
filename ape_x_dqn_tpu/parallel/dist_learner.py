"""Multi-chip learners: replay-sharded data parallelism + tensor-parallel
dense layers over a (dp, tp) mesh.

Reference parity (SURVEY.md §2.3): the reference's NCCL grad all-reduce
becomes an XLA-inserted psum over ICI; its host sum-tree becomes dp
per-shard device sum-trees.

Design (the "pick a mesh, annotate shardings, let XLA insert
collectives" recipe):
- Replay state carries a leading [dp] axis on every array (storage
  [dp, cap_shard, ...], tree [dp, 2*cap_shard], pos/size/rng [dp]),
  sharded `P("dp")`. Replay ops are `jax.vmap`s of the single-shard
  pure functions — under GSPMD each mesh row executes only its own
  slice, so sampling/priority-updates never cross ICI.
- Each shard draws batch/dp samples from its own tree (stratified
  within shard); IS weights use the global fill N = sum of shard sizes
  and a global max-normalization (one tiny psum).
- The loss/grad runs on the flattened [dp*b_local] batch with a
  sharding constraint P("dp"); the batch-mean makes GSPMD emit the
  gradient psum over "dp" — the NCCL all-reduce equivalent.
- Large dense kernels are column-sharded over "tp"
  (parallel.sharding.make_param_shardings); optimizer state inherits
  param shardings by initializing it under jit with sharded inputs.

Ingest expects items pre-split per shard: [dp, B_ingest, ...]. The
host-side driver round-robins actor staging units across shards.

DistLearner is runtime/learner.py's cycle with what sharding changes
overridden; like it, it runs whichever family it is given — flat
n-step transitions (SURVEY.md §3.3) or R2D2 stored-state sequences
(§3.4 — the r2d2 config attests dp=4 x tp=2). For sequences the replay
shards hold whole sequences as items (same per-shard trees); the
burn-in unroll + n-step sequence loss runs on the flattened
[dp*b_local] sequence batch — the LSTM time axis stays unsharded
(SURVEY.md §5 long-context: shard the batch axis, scan the time axis),
and the per-SEQUENCE eta-mixed |TD| writes back per shard.
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ape_x_dqn_tpu.obs import learning as learn_obs
from ape_x_dqn_tpu.replay.prioritized import PrioritizedReplay, ReplayState
from ape_x_dqn_tpu.parallel.sharding import make_param_shardings
from ape_x_dqn_tpu.runtime.learner import (
    BATCH, HEALTH, SAMPLE, LearnerFamily, SingleChipLearner)


class DistTrainState(NamedTuple):
    params: Any
    target_params: Any
    opt_state: Any
    replay: ReplayState   # every leaf has a leading [dp] axis
    rng: jax.Array        # [dp] keys
    step: jax.Array       # scalar int32


class DistLearner(SingleChipLearner):
    """The learner cycle over a (dp, tp) mesh. Every jitted training
    endpoint (train_step, train_step_k, sample_k, learn_k, train_many)
    is SingleChipLearner's; defined here is what sharding changes."""

    def __init__(self, family: LearnerFamily, replay: PrioritizedReplay,
                 lcfg, mesh: Mesh):
        """`replay` is configured with the PER-SHARD capacity."""
        super().__init__(family, replay, lcfg)
        self.mesh = mesh
        self.dp = mesh.shape["dp"]
        assert lcfg.batch_size % self.dp == 0, \
            "batch_size must divide by dp"
        self.b_local = lcfg.batch_size // self.dp
        self._dp_sharding = NamedSharding(mesh, P("dp"))
        # coalesced ingest groups [g, dp, ...]: replicate the group
        # axis, shard the dp axis (add_many)
        self._group_sharding = NamedSharding(mesh, P(None, "dp"))
        self._repl_sharding = NamedSharding(mesh, P())
        self._reshard = None  # publish_params' cached jit (built once)

    # -- state construction ------------------------------------------------

    def init(self, params: Any, item_spec: Any,
             rng: jax.Array) -> DistTrainState:
        param_shardings = make_param_shardings(params, self.mesh)

        # make_array_from_callback instead of device_put: the mesh may
        # span processes (multihost), where device_put to a non-
        # addressable sharding is an error; the callback hands each
        # process the slices it owns from its (identical, same-seed)
        # host copy
        def put(x, sharding):
            x = np.asarray(x)  # apexlint: host-sync(one-time init: host copy feeding make_array_from_callback)
            return jax.make_array_from_callback(
                x.shape, sharding, lambda idx: x[idx])

        params = jax.tree.map(put, params, param_shardings)
        target = jax.jit(partial(jax.tree.map, jnp.copy))(params)
        opt_state = jax.jit(self.optimizer.init)(params)

        def one_shard_replay(_):
            return self.replay.init(item_spec)

        # out_shardings avoids ever materializing the full replicated
        # buffer: each shard's storage is allocated on its own mesh row
        replay0 = jax.jit(
            jax.vmap(one_shard_replay),
            out_shardings=jax.tree.map(lambda _: self._dp_sharding,
                                       jax.eval_shape(
                                           jax.vmap(one_shard_replay),
                                           jnp.arange(self.dp))),
        )(jnp.arange(self.dp))
        rngs = jax.jit(lambda k: jax.random.split(k, self.dp),
                       out_shardings=self._dp_sharding)(rng)
        return DistTrainState(params, target, opt_state, replay0, rngs,
                              jnp.int32(0))

    # -- pure step ---------------------------------------------------------

    def _sample_weighted(self, replay_state: ReplayState, sk,
                         n_per_shard, chunks: int = 1):
        """Per-shard stratified sample of n_per_shard items + global IS
        weights over the [dp, n_per_shard] pool.

        sample_items delegates storage reconstruction to the replay —
        flat layouts gather rows, the frame-ring layout rebuilds stacks
        from single frames (replay/frame_ring.py); the size clamp keeps
        a sparsely-filled shard's descent off zero-priority leaves.

        IS weights against the ACTUAL sampling distribution: a draw
        lands in each shard with probability 1/dp (stratified — every
        shard contributes exactly n_per_shard draws) and within shard d
        on item i with probs = p_i/m_d, so P(i) = probs/dp EXACTLY, even
        with skewed shard masses. At beta=1 the weighted estimate is
        therefore unbiased toward the uniform target regardless of
        skew (tests/test_parallel.py::test_skewed_shard_is_weights —
        weighting by the single-global-tree probability p_i/M instead
        would bias each shard's contribution by M/(dp*m_d)). What
        skew DOES perturb is the sampling distribution itself: items
        in a starved shard are over-sampled (and down-weighted);
        round-robin ingest keeps masses balanced in expectation, so
        the effective prioritization tracks the single-tree recipe.

        Takes the replay state alone (not the full train state): like
        the single-chip replay's sample_state it reads only
        storage/tree/size, so a prefetched call commutes with an
        in-flight per-shard priority write-back (the double-buffering
        contract, runtime/learner.py).

        Returns (items [dp, n, ...], idx [dp, n], w [dp, n]) with w
        NOT yet max-normalized (callers normalize per training batch);
        every shard's draw is in stratum order, or chunk-major with
        `chunks`=K (ops/sum_tree.py::sample).
        """
        def shard_sample(rstate: ReplayState, key):
            return self.replay.sample_items(rstate, key, n_per_shard,
                                            chunks)

        items, idx, probs = jax.vmap(shard_sample)(replay_state, sk)
        n_global = jnp.maximum(
            replay_state.size.astype(jnp.float32).sum(), 1.0)
        w = (n_global * jnp.maximum(probs / self.dp, 1e-12)
             ) ** (-self.replay.beta)
        # dead frame-ring pad slots (prob ~0) would dominate the max-
        # normalization; they train with weight 0 instead
        w = w * jax.vmap(self.replay.valid_mask)(replay_state, idx)
        return items, idx, w

    def _flat(self, x):
        y = x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])
        return jax.lax.with_sharding_constraint(y, self._dp_sharding)

    def _sgd_step(self, params, target_params, opt_state, step,
                  items, w, want_tree_diag=True):
        """One SGD step on an already-sampled [dp, b_local] batch.
        `w` is the raw IS weight ([dp, b_local]); max-normalization
        happens here so each training batch is normalized over exactly
        its own draws. The loss runs on the flattened batch under the
        dp sharding constraint (GSPMD emits the gradient psum); the
        |TD|s go back per shard."""
        with jax.named_scope(BATCH):
            w = w / jnp.maximum(w.max(), 1e-12)
            batch = self.family.make_batch(
                jax.tree.map(self._flat, items))
            w = self._flat(w)
        params, target_params, opt_state, step, td_abs, metrics = \
            self._sgd_update(params, target_params, opt_state, step,
                             batch, w, want_tree_diag)
        with jax.named_scope(HEALTH):
            td_shard = td_abs.reshape(self.dp, self.b_local)
            # the flat reductions inside _sgd_update's diag run over the
            # [dp]-sharded batch, so GSPMD lowers them to the psum'd
            # GLOBAL statistics; the per-shard mean-|TD| min/max exposes
            # shard skew the global mean would average away
            shard_means = td_shard.mean(axis=1)
            metrics["diag"]["shard_td_mean_min"] = shard_means.min()
            metrics["diag"]["shard_td_mean_max"] = shard_means.max()
        return params, target_params, opt_state, step, td_shard, metrics

    def _replay_health(self, replay_state: ReplayState, idx, pri_then):
        return learn_obs.replay_health_sharded(
            self.replay, replay_state, idx, pri_then)

    def _write_back(self, replay_state: ReplayState, idx, td_parts):
        """Per-shard priority write-back: the [dp, b_local] parts pair
        with idx[:, j*b_local:(j+1)*b_local]."""
        return jax.vmap(self.replay.update_priorities)(
            replay_state, idx, jnp.concatenate(td_parts, axis=1))

    @jax.named_scope(SAMPLE)
    def _sample_stage(self, replay_state: ReplayState, sk, k: int):
        """Pure SAMPLE stage of the split K-batch cycle, dist form of
        SingleChipLearner._sample_stage: one per-shard stratified
        K*b_local descent + gather + global IS weights, chunked for
        the K SGD steps.

        Order of the draw: CHUNK-MAJOR within every shard — position
        j*b_local + i = stratum i*K + j (ops/sum_tree.py::chunk_major
        holds the permutation and why the strata interleave), so chunk
        j is the contiguous block [:, j*b_local:(j+1)*b_local] of
        everything gathered: the indices are permuted, never the
        sampled payload. [K, dp, b_local, ...] is a stack of those K
        blocks (not a reshape + moveaxis, which XLA does not fold:
        see SingleChipLearner._sample_stage), so `items_k[j]` is block
        j itself on every chip.

        -> (items_k [K, dp, b_local, ...], idx [dp, K*b_local]
        UN-chunked (in the draw's own order) for the per-shard
        write-back, w_k [K, dp, b_local] raw — _sgd_step
        max-normalizes per training batch, and pri [dp, K*b_local]
        descent-time leaf priorities appended LAST for the staleness
        delta — positional readers of the tuple's stable prefix are
        unmoved)."""
        items, idx, w = self._sample_weighted(replay_state, sk,
                                              k * self.b_local, chunks=k)
        pri = jax.vmap(self.replay.leaf_priorities)(replay_state, idx)

        def split(x):
            # [dp, k*b_local, ...] -> [k, dp, b_local, ...]
            return jnp.stack(jnp.split(x, k, axis=1))

        return jax.tree.map(split, items), idx, split(w), pri

    def _split_rng(self, rng):
        """[dp] keys -> ([dp] advanced, [dp] subkeys)."""
        keys = jax.vmap(lambda kk: jax.random.split(kk, 2))(rng)
        return keys[:, 0], keys[:, 1]

    # -- jitted endpoints (the training ones are inherited) ----------------

    @partial(jax.jit, static_argnums=0, donate_argnums=1)
    def add(self, state: DistTrainState, items: Any,
            td_abs: jax.Array) -> DistTrainState:
        """items: pytree of [dp, B, ...]; td_abs: [dp, B].

        add_lockstep, NOT jax.vmap(add): vmap batches the in-place
        dynamic_update_slice ring write into a lax.scatter, which
        materializes a full shard-storage copy per add (the exact HLO
        temp the packed-row layout eliminated — replay/packing.py). The
        lockstep form exploits the dist ingest contract (equal [dp, B]
        blocks every add -> equal shard cursors) to write all shards
        with one in-place multi-axis DUS.
        """
        items = jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(
                jnp.asarray(x), self._dp_sharding), items)
        return state._replace(
            replay=self.replay.add_lockstep(state.replay, items, td_abs))

    @partial(jax.jit, static_argnums=0, donate_argnums=1)
    def add_many(self, state: DistTrainState, items: Any,
                 td_abs: jax.Array) -> DistTrainState:
        """Coalesced ingest: items [g, dp, B, ...], td_abs [g, dp, B] —
        g staged blocks fused into ONE donated dispatch, so the driver
        takes _state_lock once per group instead of once per block and
        a burst of ingest stops interleaving small add dispatches with
        train_many (runtime/ingest.py).

        UNROLLED Python loop over the static g axis, not lax.scan: a
        scan carrying the replay storage re-materializes the full
        storage per iteration on the CPU backend (PERF.md "CPU scan
        pathology"), while the unrolled chain keeps each add_lockstep's
        in-place multi-axis DUS aliasing on every backend. g is small
        (ingest_coalesce), so trace/compile cost is negligible.
        """
        items = jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(
                jnp.asarray(x), self._group_sharding), items)
        rs = state.replay
        for j in range(td_abs.shape[0]):
            rs = self.replay.add_lockstep(
                rs, jax.tree.map(lambda x, j=j: x[j], items), td_abs[j])
        return state._replace(replay=rs)

    # -- tiered cold store endpoints (runtime/driver.py eviction cycle;
    # per-shard directed form — each shard evicts its OWN lowest-mass
    # region, so the tier runs on the dp-sharded ring) -------------------

    @partial(jax.jit, static_argnums=(0, 2))
    def evict_region(self, state: DistTrainState, block: int):
        """-> (start [dp], staging-layout items [dp, block, ...],
        stored leaf priorities [dp, ...]) — shard d's lowest-priority-
        mass `block`-unit region, planned independently per shard. NOT
        donated: the driver fetches the result to host (ColdStore.put
        per shard) before add_at overwrites the regions in place.
        evict_plan/read_region are pure reads, so jax.vmap is safe
        here — the scatter-rebatch hazard only bites donated in-place
        writes (add_at below uses the unrolled per-shard DUS form)."""
        def plan_read(rs):
            start = self.replay.evict_plan(rs, block)
            items, pri = self.replay.read_region(rs, start, block)
            return start, items, pri
        return jax.vmap(plan_read)(state.replay)

    @partial(jax.jit, static_argnums=0, donate_argnums=1)
    def add_at(self, state: DistTrainState, items: Any,
               td_abs: jax.Array, start: jax.Array) -> DistTrainState:
        """Directed ingest add: shard d overwrites its evict_region
        start[d] instead of the lockstep FIFO cursor (cold tier on +
        ring full; the default path never calls this)."""
        items = jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(
                jnp.asarray(x), self._dp_sharding), items)
        return state._replace(
            replay=self.replay.add_at_lockstep(state.replay, items,
                                               td_abs, start))

    # -- weight publication (learner -> inference server over ICI) --------

    def publish_params(self, state: DistTrainState) -> Any:
        """Fully-replicated param copy for the actor inference server.

        The tp all-gather happens over ICI (XLA resharding), mirroring
        the reference's learner->actor weight broadcast (SURVEY.md §2.3
        item 3), without interrupting train_many dispatches.

        The resharding runs under jit with replicated out_shardings —
        the multihost-safe form (device_put cannot target non-
        addressable shardings), and the jit's fresh output buffers also
        make the copy donation-safe: the learner jits donate the
        TrainState, so an aliased publication would hand the inference
        server buffers that the next add/train_step deletes.
        """
        if self._reshard is None:
            # built once: a fresh jax.jit wrapper per publish would
            # retrace/recompile on the hot weight-broadcast path
            self._reshard = jax.jit(
                partial(jax.tree.map, jnp.copy),
                out_shardings=jax.tree.map(
                    lambda _: self._repl_sharding, state.params))
        return self._reshard(state.params)

    # -- per-shard observability -------------------------------------------

    def shard_stats(self, state: DistTrainState) -> dict:  # apexlint: host-sync(documented off the hot loop: teardown, publish boundaries)
        """Per-shard replay fill/sample statistics for the obs plane
        and the run report:

        - sizes: ring occupancy per shard in the replay's native item
          units (transitions for flat/frame-ring, sequences for R2D2);
        - live: live item count per shard (frame-ring layouts exclude
          dead episode-pad slots via `live_transitions`; other layouts
          report sizes);
        - fill: sizes / per-shard capacity;
        - tree_mass: per-shard sum-tree root — the stratified-sampling
          denominator. Skew here is IS-weight skew (down-weighted by
          the global-N recipe in _sample_weighted), not an error.

        Host-side device fetch; call off the hot loop (teardown,
        publish boundaries)."""
        rs = state.replay
        sizes = np.asarray(rs.size).reshape(-1).astype(np.int64)
        live = sizes
        if hasattr(self.replay, "live_transitions"):
            live = np.asarray(self.replay.live_transitions(rs)
                              ).reshape(-1).astype(np.int64)
        cap = float(max(int(self.replay.capacity), 1))
        # tree layout is [dp, 2*cap] with the root mass at index 1
        mass = np.asarray(rs.tree[:, 1]).astype(np.float64)
        fill = sizes / cap
        return {
            "sizes": sizes.tolist(),
            "live": live.tolist(),
            "fill": [round(float(f), 6) for f in fill],
            "tree_mass": [round(float(m), 4) for m in mass],
            "fill_min": float(fill.min()),
            "fill_max": float(fill.max()),
        }
