"""The learner: sample -> loss -> update -> priority write-back, one jit.

This is the reference's hot loop (SURVEY.md §3.3) rebuilt TPU-first: the
reference fuses forward/backward/optimizer in CUDA and keeps its sum-tree
on the host; here the *entire* cycle — stratified sum-tree sampling,
batch gather from HBM storage, n-step double-DQN Huber loss, optimizer
update, priority write-back, and periodic target sync — is one XLA graph
with the learner state donated (no host round-trips, no copies).

`train_many` is a `lax.scan` over the steps of one dispatch, so the
device runs unattended for `train_chunk` grad-steps — this is what the
offline cells of the benchmark (benchmarks/) measure.

Replay ingest (`add`) is a separate donated jit: the actor/ingest thread
feeds device-resident storage while the learner thread owns training.

The cycle is written once, here. What a model family brings is a value
(`LearnerFamily`: a loss and an items -> batch function; the table that
builds one from a RunConfig is runtime/family.py); what sharding
changes is a set of overrides (parallel/dist_learner.py). DPGLearner
(two nets, two optimizers, soft targets, no K-batch) is the one learner
still written apart.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax

from ape_x_dqn_tpu.obs import learning as learn_obs
from ape_x_dqn_tpu.replay.prioritized import ReplayState


class TrainState(NamedTuple):
    params: Any
    target_params: Any
    opt_state: Any
    replay: ReplayState
    rng: jax.Array
    step: jax.Array  # int32 grad-step counter


class LearnerFamily(NamedTuple):
    """What a model family brings to the learner cycle, bound once
    (runtime/family.py::learner_family)."""
    name: str
    # (params, target_params, batch, is_weights) -> (loss, aux); aux
    # carries "td_abs", the priorities the cycle writes back
    loss_fn: Callable
    make_batch: Callable  # sampled items pytree -> the loss's batch
    net_apply: Callable
    # attribute under which the learner exposes net_apply: the name
    # says the signature (net_apply(params, obs) -> q; net_apply_seq
    # (params, obs[B,T,...], (c,h)) -> (q[B,T,A], state))
    apply_attr: str = "net_apply"
    # aux scalars the family adds to every step's metrics
    metric_keys: tuple[str, ...] = ()


def transition_item_spec(obs_shape, obs_dtype) -> dict:
    """Item pytree spec for one flat n-step transition (discrete actions)."""
    return {
        "obs": jax.ShapeDtypeStruct(obs_shape, obs_dtype),
        "action": jax.ShapeDtypeStruct((), jnp.int32),
        "reward": jax.ShapeDtypeStruct((), jnp.float32),
        "next_obs": jax.ShapeDtypeStruct(obs_shape, obs_dtype),
        "discount": jax.ShapeDtypeStruct((), jnp.float32),
    }


ADAM_B1, ADAM_B2 = 0.9, 0.999


def make_optimizer(lcfg) -> optax.GradientTransformation:
    return optax.chain(
        optax.clip_by_global_norm(lcfg.max_grad_norm),
        optax.adam(lcfg.lr, b1=ADAM_B1, b2=ADAM_B2, eps=lcfg.adam_eps),
    )


def applied_update(lcfg, opt_state):
    """The update `make_optimizer(lcfg)` applied on the step that left
    `opt_state`, rebuilt from Adam's NEW moments by optax's own formula
    (`scale_by_adam`, then `scale(-lr)`). The moments live in HBM
    anyway, so a diagnostic under a `lax.cond` can take them as
    operands where the update tree itself would have to be written out
    on every step to be one (obs/learning.py::sgd_diag)."""
    adam = opt_state[1][0]
    mu_hat = optax.tree.bias_correction(adam.mu, ADAM_B1, adam.count)
    nu_hat = optax.tree.bias_correction(adam.nu, ADAM_B2, adam.count)
    return jax.tree.map(
        lambda m, v: -lcfg.lr * (m / (jnp.sqrt(v) + lcfg.adam_eps)),
        mu_hat, nu_hat)


# The cycle's own parts as `jax.named_scope`s: seven disjoint names that
# tile a grad step, each opened at the one place its stage is entered.
# Op metadata only (the lowered program is the same text without debug
# info), so they cost nothing on the device; in a `jax.profiler` trace
# every device op carries the name of its stage, and
# benchmarks/harness/cycle_scopes.py sums self time by name. What a
# trace shows under none (the scans' own `while` time, `_split_rng`,
# copies XLA inserts without metadata) is the account's residual.
# ops/sum_tree.py nests `sum_tree.descent` / `sum_tree.update` inside
# the first and the last. A learner that overrides a stage opens the
# SAME name from this tuple (parallel/dist_learner.py,
# runtime/dpg_learner.py). The persistent compile cache's key leaves
# debug info out: an executable cached before a scope was added or
# renamed is loaded again and shows the OLD names, so empty the cache
# before profiling such an edit (README "Observability").
CYCLE_SCOPES = (
    "cycle.sample",       # descent, storage gather, IS weights, K-split
    "cycle.batch",        # items -> the family's batch
    "cycle.loss_grad",    # the family's loss, forward and backward
    "cycle.optimizer",    # optimizer.update + apply_updates
    "cycle.target_sync",  # the cond / select over the target tree
    "cycle.health",       # what the metrics inside the step cost
    "cycle.write_back",   # the one priority write-back
)
(SAMPLE, BATCH, LOSS_GRAD, OPTIMIZER, TARGET_SYNC, HEALTH,
 WRITE_BACK) = CYCLE_SCOPES


# Parameter bytes from which the SGD tail branches (a `lax.cond` for the
# target sync and for the tree-sized health norms) instead of passing
# over the trees on every step. A branch is not free: it ends Adam's
# fusion with what followed it and gives the loop's carry other
# layouts, so a small net loses more than the passes cost. Measured on
# a v5e (PERF.md §6, PR 31): 6.8 MB of parameters -2.8% (pong_offline),
# 15 MB +1.0% (r2d2_offline), 2.4 GB +3.9% (glm47_flash_offline).
TAIL_BRANCH_MIN_BYTES = 8 << 20


def _last_of(length: int, returned: bool = True):
    """The xs of a train_many scan: True on the iteration whose metrics
    train_many returns (the last one of the last scan), False on the
    others."""
    return (jnp.arange(length) == length - 1) & returned


class SingleChipLearner:
    """The learner cycle on one chip: state init, the exact per-step
    path, the K-batch relaxation, the train_many scan, the ingest add,
    and param publication — for whichever `family` it is given.

    The K-batch semantics (interleaved strata, per-chunk IS renorm, one
    write-back, remainder-first metrics) are written here and nowhere
    else, so they cannot drift between families or between one chip and
    the mesh: the sharded learner (parallel/dist_learner.py) inherits
    every jitted endpoint and overrides only what sharding changes —
    `_split_rng`, `_sample_weighted`, `_sample_stage`, `_sgd_step`,
    `_replay_health`, `_write_back`, and the state/ingest/publication
    endpoints. A difference between the two is such a method, never a
    branch on who is calling.

    The K-batch cycle itself is split into two pure stages —
    _sample_stage (stratified K*B descent + gather + chunked IS
    weights) and _learn_stage (K SGD steps + one write-back + target
    sync) — composed back-to-back by the fused path and pipelined
    one-deep by the double-buffered path (sample_prefetch).
    """

    def __init__(self, family: LearnerFamily, replay, lcfg):
        self.family = family
        setattr(self, family.apply_attr, family.net_apply)
        self.replay = replay
        self.lcfg = lcfg
        # always make_optimizer's: applied_update rebuilds ITS update
        self.optimizer = make_optimizer(lcfg)
        # draws per shard and training batch; one shard here
        self.b_local = lcfg.batch_size

    # -- state ------------------------------------------------------------

    def init(self, params: Any, replay_state: ReplayState,
             rng: jax.Array) -> TrainState:
        return TrainState(
            params=params,
            # real copies: params and target_params are donated together,
            # so they must not alias the same device buffers
            target_params=jax.tree.map(jnp.copy, params),
            opt_state=self.optimizer.init(params),
            replay=replay_state,
            rng=rng,
            step=jnp.int32(0))

    # -- core step (pure) -------------------------------------------------

    def _split_rng(self, rng):
        """-> (advanced rng, the draw's subkey)."""
        return jax.random.split(rng)

    def _sample_weighted(self, replay_state: ReplayState, sk, n: int,
                         chunks: int = 1):
        """-> (items, idx, IS weights) of one stratified draw of n,
        max-normalized over the draw (replay.sample). Reads only the
        replay state, via `replay.sample_state`, which never touches
        the write cursor."""
        return self.replay.sample_state(replay_state, sk, n, chunks)

    def _sgd_update(self, params, target_params, opt_state, step,
                    batch, w, want_tree_diag=True):
        """One loss/grad/optimizer/target-sync update on a batch and
        IS weights already in the form the family's loss takes: the
        SGD body of every learner but DPG's, called by each stack's
        `_sgd_step` after it has prepared the two. Returns the
        family's |TD| priorities (aux['td_abs']).

        From TAIL_BRANCH_MIN_BYTES of parameters on, the tail passes
        over a parameter-sized tree only when the pass has a consumer:
        the target sync is a branch, and the health norms over trees
        run when `want_tree_diag` — True where this step's metrics
        leave the program, False (or a traced flag, in train_many's
        scans) where they are dropped. Under it the sync is a select
        and a traced flag counts as True: the passes are cheaper than
        the branches."""
        with jax.named_scope(LOSS_GRAD):
            (loss, aux), grads = jax.value_and_grad(
                self.family.loss_fn, has_aux=True)(
                params, target_params, batch, w)
        with jax.named_scope(OPTIMIZER):
            updates, opt_state = self.optimizer.update(
                grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        # hard target sync every K steps (SURVEY.md §3.3). On a big tree
        # a branch, not a select: `where` read two trees and wrote one on
        # every step (7.1 GB, 9 ms of glm47_flash_offline's 275) to sync
        # 1 in 2,500
        with jax.named_scope(TARGET_SYNC):
            step = step + 1
            sync = (step % self.lcfg.target_sync_every == 0)
            if sum(x.nbytes for x in jax.tree.leaves(params)) \
                    >= TAIL_BRANCH_MIN_BYTES:
                target_params = jax.lax.cond(
                    sync, lambda t, p: p, lambda t, p: t,
                    target_params, params)
            else:
                target_params = jax.tree.map(
                    lambda t, p: jnp.where(sync, p, t),
                    target_params, params)
                want_tree_diag = want_tree_diag is not False
        with jax.named_scope(HEALTH):
            # the one gradient norm: the clip's is this expression too
            grad_norm = optax.global_norm(grads)
            metrics = {
                "loss": loss,
                "q_mean": aux["q_mean"],
                "td_abs_mean": aux["td_abs"].mean(),
                **{key: aux[key] for key in self.family.metric_keys},
                "grad_norm": grad_norm,
                # learning-health scalars (obs/learning.py); rides the
                # metrics pytree through every scan, read at existing
                # host sync points only. The update is rebuilt from the
                # new opt_state: `updates` itself never outlives the
                # apply
                "diag": learn_obs.sgd_diag(
                    aux, w, grads, opt_state, params,
                    grad_norm=grad_norm, want_tree_diag=want_tree_diag,
                    update_of=partial(applied_update, self.lcfg)),
            }
        return params, target_params, opt_state, step, aux["td_abs"], \
            metrics

    def _sgd_step(self, params, target_params, opt_state, step,
                  items, is_w, want_tree_diag=True):
        """One SGD step on already-sampled items (shared by the exact
        per-step path and the K-batch relaxation)."""
        with jax.named_scope(BATCH):
            batch = self.family.make_batch(items)
        return self._sgd_update(params, target_params, opt_state, step,
                                batch, is_w, want_tree_diag)

    def _replay_health(self, replay_state: ReplayState, idx, pri_then):
        return learn_obs.replay_health(self.replay, replay_state, idx,
                                       pri_then)

    def _write_back(self, replay_state: ReplayState, idx, td_parts):
        """The ONE priority write-back of a step or macro-step.
        `td_parts` holds each SGD step's |TD|s, in the order of `idx`'s
        chunks. Touches only the sum-tree (`replay.update_state`),
        which is what lets a prefetched draw be reordered against it."""
        return self.replay.update_state(replay_state, idx.reshape(-1),
                                        jnp.concatenate(td_parts))

    def _train_step(self, state: TrainState,
                    want_tree_diag=True) -> tuple[TrainState, dict]:
        rng, sk = self._split_rng(state.rng)
        with jax.named_scope(SAMPLE):
            items, idx, w = self._sample_weighted(state.replay, sk,
                                                  self.b_local)
        params, target_params, opt_state, step, td_abs, metrics = \
            self._sgd_step(state.params, state.target_params,
                           state.opt_state, state.step, items, w,
                           want_tree_diag)
        # fused path: draw and write-back see the same tree, so the
        # priority-staleness delta is identically 0 (pri_then=None)
        with jax.named_scope(HEALTH):
            metrics["diag"] = {
                **metrics.get("diag", {}),
                **self._replay_health(state.replay, idx, None)}
        with jax.named_scope(WRITE_BACK):
            replay_state = self._write_back(state.replay, idx, [td_abs])
        new_state = state._replace(
            params=params, target_params=target_params,
            opt_state=opt_state, replay=replay_state, rng=rng, step=step)
        return new_state, metrics

    @jax.named_scope(SAMPLE)
    def _sample_stage(self, replay_state: ReplayState, sk: jax.Array,
                      k: int):
        """Pure SAMPLE stage of the (split) K-batch cycle: one
        stratified K*B tree descent + storage gather + IS weights,
        already chunked for the K SGD steps. Reads only the replay
        state (_sample_weighted), so a prefetched call commutes with an
        in-flight priority write-back — the double-buffering contract.

        Order of the draw: CHUNK-MAJOR. The replay emits position
        j*B + i = stratum i*K + j (ops/sum_tree.py::chunk_major holds
        the permutation and why the strata interleave), so chunk j is
        the contiguous block [j*B, (j+1)*B) of everything gathered:
        the K*B indices are permuted (a few kB), never the sampled
        payload. The [K, B, ...] view is a stack of those K blocks, not
        a reshape — the first conv reads the batch in the lanes, so a
        reshaped [K*B] array makes chunk j a slice of the lane
        dimension (measured slower than the parent); as a stack of
        slices XLA folds `items_k[j]` back to block j itself, which a
        layout that gathers per chunk (FrameRingReplay._gather) hands
        over as it lands (PERF.md §6, PR 25).

        -> (items_k [K, B, ...] pytree, idx_k [K, B], is_w_k [K, B],
            pri_k [K, B] descent-time leaf priorities — the staleness
            reference _learn_stage compares against at write-back time;
            appended LAST so positional readers of the tuple's stable
            prefix, e.g. single_process.py's `sample[1]`, are unmoved)
        """
        items, idx, is_w = self._sample_weighted(
            replay_state, sk, k * self.b_local, chunks=k)
        pri = self.replay.leaf_priorities(replay_state, idx)

        def split(x):
            return jnp.stack(jnp.split(x, k))

        # sample() max-normalized over the K*B pool; renormalizing per
        # chunk recovers the exact per-step IS convention
        is_w_k = split(is_w)
        is_w_k = is_w_k / jnp.maximum(
            is_w_k.max(axis=1, keepdims=True), 1e-12)
        return jax.tree.map(split, items), split(idx), is_w_k, split(pri)

    def _learn_stage(self, state: TrainState, sample, k: int,
                     want_tree_diag=True) -> tuple[TrainState, dict]:
        """Pure LEARN stage: K SGD steps over an already-drawn sample
        + ONE priority write-back + target sync. `state.rng` must
        already be advanced past the draw that produced `sample`.
        Step j trains on `sample`'s chunk j and its |TD|s pair with
        chunk j of the drawn indices: both sides of the write-back are
        in the draw's chunk-major order (_sample_stage), so the K parts
        are joined as they come (_write_back) and nothing is
        un-permuted — the same (leaf, |TD|) pairs stratum order and an
        inverse chunk transform gave; only the order among duplicate
        leaves inside one `.at[].set` differs, which XLA never
        specified.

        The K chunks run as a STATIC unrolled loop, not lax.scan: K is
        small (4-8) and measured on CPU a scanned conv body ran ~17x
        slower than the identical straight-line code (855 vs 51
        ms/step — scan's carried buffers defeat in-place aliasing
        there), while unrolled code also gives XLA's scheduler the
        whole window to overlap. Only step K-1's metrics are returned,
        so the others are told not to make their tree-sized ones."""
        items_k, idx, w_k, pri = sample
        params, target_params, opt_state, step = (
            state.params, state.target_params, state.opt_state,
            state.step)
        td_parts = []
        metrics = None
        for j in range(k):
            with jax.named_scope(BATCH):
                it, w = jax.tree.map(lambda x: x[j], (items_k, w_k))
            params, target_params, opt_state, step, td_abs, metrics = \
                self._sgd_step(params, target_params, opt_state, step,
                               it, w,
                               want_tree_diag if j == k - 1 else False)
            td_parts.append(td_abs)
        # write-back-time replay health: state.replay's tree is what
        # the sampler would see NOW, pri is what it saw at descent
        # time — their delta is the measured priority staleness the
        # prefetch/K-batch relaxations accept (ROADMAP item 3)
        with jax.named_scope(HEALTH):
            metrics["diag"] = {
                **metrics.get("diag", {}),
                **self._replay_health(state.replay, idx, pri)}
        with jax.named_scope(WRITE_BACK):
            replay_state = self._write_back(state.replay, idx, td_parts)
        new_state = state._replace(
            params=params, target_params=target_params,
            opt_state=opt_state, replay=replay_state, step=step)
        return new_state, metrics

    def _train_step_k(self, state: TrainState, k: int,
                      want_tree_diag=True) -> tuple[TrainState, dict]:
        """K grad-steps from ONE stratified sample + ONE priority
        write-back (the K-batch relaxation, LearnerConfig.sample_chunk).

        Chunk j+1 trains on priorities that predate chunk j's TD errors
        — the same staleness the reference's async host-side replay
        server exhibits between its sampler and learner. The payoff:
        the K SGD steps carry no tree dependency between them, so XLA
        overlaps the single big descent/gather/write-back with K steps
        of MXU work instead of serializing tree<->loss every step.

        Composed from the split _sample_stage/_learn_stage so the fused
        path and the double-buffered path (sample_prefetch) cannot
        drift."""
        rng, sk = self._split_rng(state.rng)
        sample = self._sample_stage(state.replay, sk, k)
        return self._learn_stage(state._replace(rng=rng), sample, k,
                                 want_tree_diag)

    # -- jitted endpoints --------------------------------------------------

    @partial(jax.jit, static_argnums=0, donate_argnums=1)
    def train_step(self, state: TrainState):
        return self._train_step(state)

    @partial(jax.jit, static_argnums=(0, 2), donate_argnums=1)
    def train_step_k(self, state: TrainState, k: int):
        """One K-batch macro-step WITHOUT the outer train_many scan.
        The inner scan carries only (params, targets, opt, step) — on
        backends where lax.scan cannot alias a large carried buffer
        in place (CPU), train_many's outer scan copies the whole
        replay storage every iteration; this endpoint avoids that
        (the single-process driver uses it for the K-batch path)."""
        return self._train_step_k(state, k)

    @partial(jax.jit, static_argnums=(0, 2))
    def sample_k(self, state: TrainState, k: int):
        """Standalone SAMPLE dispatch for the host-side double-buffer
        pipeline (single_process.py): draw the NEXT macro-step's
        chunked sample from the current tree. Deliberately NOT donated
        — the caller keeps `state` alive for the learn_k that trains on
        the PREVIOUS draw. -> (sample, advanced rng)."""
        rng, sk = self._split_rng(state.rng)
        return self._sample_stage(state.replay, sk, k), rng

    @partial(jax.jit, static_argnums=(0, 3), donate_argnums=(1,))
    def learn_k(self, state: TrainState, sample, k: int):
        """Standalone LEARN dispatch: K SGD steps + write-back on a
        sample drawn earlier by sample_k (possibly against a tree that
        an `add` or a previous write-back has since changed — the
        accepted async-replay staleness). state.rng must be the rng
        sample_k returned. Only the state is donated — the sample's
        buffers match no output shape (XLA would warn them unusable)
        and are freed when the caller drops its reference."""
        return self._learn_stage(state, sample, k)

    @partial(jax.jit, static_argnums=(0, 2), donate_argnums=1)
    def train_many(self, state: TrainState, n: int):
        """n grad-steps in one dispatch via lax.scan (the program the
        offline cells of benchmarks/ time, found by this name).
        With sample_chunk=K>1, runs n//K K-batch macro-steps (plus
        exact single steps for any remainder) — same grad-step count
        either way. With sample_prefetch, the macro-step scan runs
        double-buffered (see _train_many_prefetch).

        Only the LAST step's metrics leave the program, so every scan
        runs over `_last_of(length)` and hands that flag down as
        `want_tree_diag`: XLA cannot prune a scan body, and the norms
        over parameter-sized trees are 2.9 ms a tree at 591 M
        parameters (obs/learning.py::sgd_diag)."""
        k = self.lcfg.sample_chunk

        def body(s, last):
            return self._train_step(s, last)

        if self.lcfg.sample_prefetch:
            return self._train_many_prefetch(state, n, max(k, 1), body)

        if k <= 1:
            state, metrics = jax.lax.scan(body, state, _last_of(n))
            return state, jax.tree.map(lambda x: x[-1], metrics)

        def body_k(s, last):
            return self._train_step_k(s, k, last)

        # exact singles for the remainder run FIRST so the returned
        # (last-step) metrics come from the K-batch macro-steps that do
        # the bulk of the dispatch's work — remainder-last returned only
        # the singles' metrics, hiding K-batch pathologies from the
        # driver log exactly where they'd show (round-4 verdict weak #7)
        metrics = None
        if n % k:
            state, metrics = jax.lax.scan(
                body, state, _last_of(n % k, returned=not n // k))
        if n // k:
            state, metrics = jax.lax.scan(body_k, state, _last_of(n // k))
        return state, jax.tree.map(lambda x: x[-1], metrics)

    def _train_many_prefetch(self, state: TrainState, n: int, k: int,
                             body):
        """Double-buffered macro-step pipeline (the tentpole,
        LearnerConfig.sample_prefetch): inside the scan body, the NEXT
        macro-step's sample is drawn from the tree BEFORE this
        macro-step's K SGD steps and priority write-back run. The draw
        and the SGD/write-back then share no data dependency, so XLA's
        scheduler is free to overlap the next tree descent + storage
        gather with the current backward passes — the overlap the fused
        body only achieves within one macro-step.

        Staleness contract: the sample for macro-step i+1 sees
        priorities that predate macro-step i's write-back — one
        dispatch of lag, the same kind the K-batch relaxation already
        accepts within a macro-step and the reference's async
        host-side sampler exhibits always. The first macro-step trains
        on a fresh (prologue) draw, so a single-macro-step dispatch is
        bit-identical in params to train_step_k; the final prefetched
        sample is discarded (one extra K*B descent per dispatch,
        amortized over n//k macro-steps)."""
        metrics = None
        if n % k:
            state, metrics = jax.lax.scan(
                body, state, _last_of(n % k, returned=not n // k))
        if n // k:
            rng, sk = self._split_rng(state.rng)
            pending = self._sample_stage(state.replay, sk, k)
            state = state._replace(rng=rng)

            def body_pf(carry, last):
                s, pend = carry
                rng, sk = self._split_rng(s.rng)
                # drawn BEFORE _learn_stage's write-back: no data
                # dependency with the K SGD steps below
                nxt = self._sample_stage(s.replay, sk, k)
                s, m = self._learn_stage(s._replace(rng=rng), pend, k,
                                         last)
                return (s, nxt), m

            (state, _), metrics = jax.lax.scan(
                body_pf, (state, pending), _last_of(n // k))
        return state, jax.tree.map(lambda x: x[-1], metrics)

    @partial(jax.jit, static_argnums=0, donate_argnums=1)
    def add(self, state: TrainState, items: Any,
            td_abs: jax.Array) -> TrainState:
        return state._replace(
            replay=self.replay.add(state.replay, items, td_abs))

    @partial(jax.jit, static_argnums=0, donate_argnums=1)
    def add_many(self, state: TrainState, items: Any,
                 td_abs: jax.Array) -> TrainState:
        """Coalesced ingest: items [g, B, ...], td_abs [g, B] — g staged
        blocks fused into ONE donated dispatch, so the driver takes
        _state_lock once per group instead of once per block and a burst
        of ingest stops interleaving small add dispatches with
        train_many (runtime/ingest.py).

        UNROLLED Python loop over the static g axis, not lax.scan: a
        scan carrying the replay storage re-materializes the full
        storage per iteration on the CPU backend (PERF.md "CPU scan
        pathology"); the unrolled chain keeps each add's in-place DUS
        ring write aliasing on every backend. g is small
        (ingest_coalesce), so trace/compile cost is negligible.
        """
        rs = state.replay
        for j in range(td_abs.shape[0]):
            rs = self.replay.add(
                rs, jax.tree.map(lambda x, j=j: x[j], items), td_abs[j])
        return state._replace(replay=rs)

    # -- tiered cold store endpoints (runtime/driver.py eviction cycle) ----

    @partial(jax.jit, static_argnums=(0, 2))
    def evict_region(self, state: TrainState, block: int):
        """-> (start, staging-layout items, stored leaf priorities) of
        the ring's lowest-priority-mass `block`-unit region. NOT
        donated: the driver fetches the result to host (ColdStore.put)
        before add_at overwrites the region in place."""
        start = self.replay.evict_plan(state.replay, block)
        items, pri = self.replay.read_region(state.replay, start, block)
        return start, items, pri

    @partial(jax.jit, static_argnums=0, donate_argnums=1)
    def add_at(self, state: TrainState, items: Any, td_abs: jax.Array,
               start: jax.Array) -> TrainState:
        """Directed ingest add: overwrite the evict_region start instead
        of the FIFO cursor (cold tier on + ring full; the default path
        never calls this)."""
        return state._replace(
            replay=self.replay.add_at(state.replay, items, td_abs, start))

    def publish_params(self, state: TrainState) -> Any:
        """Independent param copy for the inference server — the train/add
        jits donate the TrainState, so aliased buffers would be deleted
        under the server's feet."""
        return jax.tree.map(jnp.copy, state.params)
