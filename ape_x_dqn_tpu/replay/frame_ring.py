"""Frame-ring prioritized replay: single frames in HBM, stacks on demand.

The flat transition layout (replay/prioritized.py) stores each n-step
transition as two full frame stacks (obs + next_obs = 2*stack frames,
~56KB at 84x84x4 uint8) — 8x redundant, since consecutive transitions
share all but one frame. That redundancy caps capacity (the attested
~2M-transition flagship replay would need ~59GB) and multiplies ingest
bytes across the wire, host->device DMA, and HBM writes (SURVEY.md §7
hard part 2 "ingest bandwidth"; §2.2 "Prioritized replay" capacity ~2M).

TPU-native fix: store each frame ONCE and reconstruct stacks with a
device-side gather at sample time (frames are uint8 in HBM; the gather
rides HBM bandwidth inside the learner jit, costing nothing extra — the
flat layout reads the same bytes, it just also *stores* them 8x).

Layout. Everything is built from fixed-size SEGMENTS so every shape is
static under jit:

- An actor cuts each episode's transition stream into segments of
  exactly B transitions (`seg_transitions`), padding the episode tail
  with dead slots (priority 0, next_off 0).
- Per episode it keeps a frame log P where P[0:stack] are the reset
  observation's channels and each env step appends one new frame; the
  step-t observation stack is then always the contiguous slice
  P[t:t+stack] (this also captures episodic-life pseudo-resets exactly,
  because the wrapper's stack carries over and so do the seeded
  channels). A transition starting at step t with span m (env steps
  between obs and bootstrap obs, ops/nstep.py) has
      obs      = P[t     : t+stack]
      next_obs = P[t+m   : t+m+stack],  1 <= m <= n_step.
- A segment covering start steps [t0, t0+B) therefore needs only the
  frames P[t0 : t0+F], F = B + n_step + stack - 1 — about (B+6)/(8B)
  of the flat layout's bytes (~6-7x less for B=16..64).

Device state reuses ReplayState: storage holds a frames ring
uint32 [S*F, pad128(H*W) // 4] — one frame per row of 32-bit words
(S = capacity/B segments) — plus per-transition fields [capacity]
(action/reward/discount/next_off);
`pos` counts SEGMENTS; the sum-tree indexes transitions. Segment k owns
transition slots [k*B, (k+1)*B) and frame rows [k*F, (k+1)*F): eviction
overwrites a whole segment at a time, so transition<->frame aliasing is
impossible by construction.

Frames are ROWS OF 32-BIT WORDS, not [H, W] planes, and adds are
contiguous dynamic_update_slice blocks with skip-to-head wrap — the two
rules that keep the ring resident in HBM at its logical size with
zero-copy add/sample graphs (see replay/packing.py for the measured OOM
story a plane layout + scatter produce at flagship capacity). Word k of
a row holds pixels 4k..4k+3 of ONE frame, least significant byte first:
the bytes of the uint8 row [pad128(H*W)] in their order (pad128 is a
multiple of 128, so always whole words), under a tiling in which a row
is a row. As uint8 the TPU packs FOUR ROWS into each 32-bit word
(T(8,128)(4,1)), so a frame was every fourth byte of the words it
shared with three neighbours and the sample gather fetched four rows
for each one it returned: 86 ns a row against 25 for the same bytes as
words (PERF.md §6, PR 29). Bytes become words on the way in
(`as_words`) and pixels again only in the sampled batch (`_gather`)
and in `read_region`; nothing else reads the leaf.

Dead padding slots carry tree priority 0 and are never sampled (the
descent clamp in ops/sum_tree.py keeps float rounding off them); their
share of capacity is <= B/(2*avg_episode_len), typically <1%. IS-weight
N counts all filled slots including dead ones — a <=1% overestimate of
N, well inside PER's tolerance (the beta anneal it feeds is itself a
heuristic).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ape_x_dqn_tpu.ops import sum_tree
from ape_x_dqn_tpu.replay.packing import (as_bytes, as_words, dus_rows,
                                          dus_rows_per_shard, frame_mode,
                                          pad128, ring_write_size,
                                          stacks_of_four)
from ape_x_dqn_tpu.replay.prioritized import (PrioritizedReplay,
                                              ReplayState, ring_cursor,
                                              ring_finish)


# THE predicate for frame-segment storage in the flat-DQN family — an
# alias of the ONE shared implementation in replay/packing.py
# (sequence_frame_mode in replay/sequence.py is the same object), so
# layout selection (runtime/family.py) and budget pricing
# (utils/hbm.py) cannot drift from each other or from the sequence
# layout. The uint8 dtype requirement is enforced with a ValueError at
# FrameRingReplay construction.
frame_ring_mode = frame_mode


def frame_segment_spec(seg_transitions: int, n_step: int,
                       obs_shape: tuple[int, ...], obs_dtype) -> dict:
    """Item pytree spec for ONE shipped segment (leading axis added by
    the ingest staging, like every other item spec)."""
    h, w, stack = obs_shape
    f = seg_transitions + n_step + stack - 1
    return {
        "seg_frames": jax.ShapeDtypeStruct((f, h, w), obs_dtype),
        "action": jax.ShapeDtypeStruct((seg_transitions,), jnp.int32),
        "reward": jax.ShapeDtypeStruct((seg_transitions,), jnp.float32),
        "discount": jax.ShapeDtypeStruct((seg_transitions,), jnp.float32),
        "next_off": jax.ShapeDtypeStruct((seg_transitions,), jnp.int32),
    }


class FrameSegmentBuilder:
    """Actor-side segment assembly (host numpy; one per actor env).

    Call order per env of an actor loop (runtime/actor.py):
      on_reset(obs)            after every env.reset()
      on_step(next_obs)        after every env.step()
      add(action, reward, discount, span, priority)
                               for each emitted n-step transition, in
                               start-step order (the actor's outbox order
                               already guarantees this)
      take_ready() -> [segment dicts] ready to ship
      flush()                  at shutdown: pad + emit the partial tail

    on_reset flushes the open partial segment first, so segments never
    span episodes and the frame-slice invariant above always holds.
    """

    def __init__(self, seg_transitions: int, n_step: int, stack: int):
        self.B = seg_transitions
        self.n = n_step
        self.stack = stack
        self.F = self.B + self.n + self.stack - 1
        self._frames: list[np.ndarray] = []  # P[base:], trimmed left
        self._base = 0          # P-index of self._frames[0]
        self._t = 0             # next transition start step (per episode)
        self._t0: int | None = None  # open segment's first start step
        self._fields: list[tuple] = []
        self._ready: list[dict] = []

    def on_reset(self, obs: np.ndarray) -> None:
        self._flush_partial()
        # seed from ALL channels: a full reset gives the wrapper's
        # zero-padded stack, an episodic-life pseudo-reset gives the
        # carried-over frames — both reconstruct exactly
        self._frames = [np.ascontiguousarray(obs[..., c])
                        for c in range(self.stack)]
        self._base = 0
        self._t = 0
        self._t0 = None

    def on_step(self, next_obs: np.ndarray) -> None:
        self._frames.append(np.ascontiguousarray(next_obs[..., -1]))

    def add(self, action, reward: float, discount: float, span: int,
            priority: float) -> None:
        assert 1 <= span <= self.n, span
        if self._t0 is None:
            self._t0 = self._t
            drop = self._t0 - self._base  # frames left of P[t0]: done with
            if drop:
                del self._frames[:drop]
                self._base = self._t0
        self._t += 1
        self._fields.append((action, float(reward), float(discount),
                             int(span), float(priority)))
        if len(self._fields) == self.B:
            self._emit()

    def _emit(self) -> None:
        s = self._t0 - self._base
        frames = self._frames[s:s + self.F]
        while len(frames) < self.F:      # episode ended early: repeat tail
            frames.append(frames[-1])
        pad = self.B - len(self._fields)
        # dead slots: priority 0 AND next_off 0 (the replay masks the
        # tree priority on next_off>0, so eps^alpha never leaks in)
        fields = self._fields + [(0, 0.0, 0.0, 0, 0.0)] * pad
        acts, rews, discs, offs, pris = zip(*fields)
        self._ready.append({
            "seg_frames": np.stack(frames)[None],
            "action": np.asarray(acts, np.int32).reshape(1, self.B),
            "reward": np.asarray(rews, np.float32).reshape(1, self.B),
            "discount": np.asarray(discs, np.float32).reshape(1, self.B),
            "next_off": np.asarray(offs, np.int32).reshape(1, self.B),
            "priorities": np.asarray(pris, np.float32).reshape(1, self.B),
        })
        self._t0 = None
        self._fields = []

    def _flush_partial(self) -> None:
        if self._fields:
            self._emit()

    def flush(self) -> list[dict]:
        self._flush_partial()
        return self.take_ready()

    def take_ready(self) -> list[dict]:
        out, self._ready = self._ready, []
        return out


class FrameRingReplay(PrioritizedReplay):
    """Device-side prioritized replay over segment storage.

    Subclasses PrioritizedReplay: `sample` (IS weights incl. the
    valid_mask dead-slot zeroing) is inherited, while storage
    construction, segment `add`, the stack-gathering `sample_items`,
    and the dead-slot-preserving `update_priorities` are overridden —
    so both learners use either layout unchanged. `add`
    consumes staged segments {field: [G, ...]} with priorities [G, B]
    instead of flat items.
    """

    def __init__(self, capacity: int, seg_transitions: int, n_step: int,
                 obs_shape: tuple[int, ...], obs_dtype=np.uint8,
                 alpha: float = 0.6, beta: float = 0.4, eps: float = 1e-6):
        super().__init__(capacity=capacity, alpha=alpha, beta=beta, eps=eps)
        # ValueError, not assert: user-config validation must survive
        # `python -O` (same rule as the multihost driver's kind check)
        if capacity % seg_transitions != 0:
            raise ValueError("segment size must divide capacity")
        if len(obs_shape) != 3:
            raise ValueError(
                f"frame-ring replay needs [H, W, stack] pixel obs, "
                f"got {obs_shape}")
        self.B = seg_transitions
        self.n = n_step
        self.h, self.w, self.stack = obs_shape
        self.F = self.B + self.n + self.stack - 1
        self.S = capacity // self.B          # segment slots
        self.frame_bytes = self.h * self.w
        self.frame_row = pad128(self.frame_bytes)
        self.row_words = self.frame_row // 4   # pad128: always whole
        self.obs_dtype = obs_dtype
        if np.dtype(obs_dtype) != np.uint8:
            raise ValueError(
                f"frame-ring word-row storage requires uint8 frames "
                f"(got {np.dtype(obs_dtype)}); use replay.storage='flat' "
                f"for non-uint8 pixel observations")

    # -- state construction ------------------------------------------------

    def init(self, item_spec: Any = None) -> ReplayState:
        """item_spec is accepted for interface parity and ignored — the
        storage layout is fixed by the constructor arguments."""
        storage = {
            "frames": jnp.zeros((self.S * self.F, self.row_words),
                                jnp.uint32),
            "action": jnp.zeros((self.capacity,), jnp.int32),
            "reward": jnp.zeros((self.capacity,), jnp.float32),
            "discount": jnp.zeros((self.capacity,), jnp.float32),
            "next_off": jnp.zeros((self.capacity,), jnp.int32),
        }
        return ReplayState(storage=storage, tree=sum_tree.init(self.capacity),
                           pos=jnp.int32(0), size=jnp.int32(0))

    # -- transitions (pure, jit-friendly) ----------------------------------

    def _write_segments(self, state: ReplayState, items: Any,
                        td_abs: jax.Array, lead: tuple[int, ...],
                        seg0: jax.Array | None = None) -> ReplayState:
        """Shared body of `add` (lead=()) and `add_lockstep`
        (lead=(dp,)): ONE contiguous dynamic_update_slice block of
        G*F frame rows / G*B transition slots per leading shard axis
        (in place on the donated state; a vmapped DUS would rebatch to
        a full-copy scatter — replay/packing.py), with skip-to-head
        wrap at the segment cursor. A caller-supplied seg0 (add_at;
        [dp]-vector seg0 under add_at_lockstep) directs the write at
        that segment instead."""
        nl = len(lead)
        g = td_abs.shape[nl]
        per_shard = False
        if seg0 is None:
            # cursor counts SEGMENTS, size counts transitions (size_scale)
            seg0, pos1, size1 = ring_cursor(state.pos, state.size, g,
                                            self.S, nl, size_scale=self.B)
        else:
            # directed write (add_at / add_at_lockstep). Dist form:
            # seg0 is a [dp] vector (each shard's own evict_plan) and
            # the cursor math is elementwise over shards.
            per_shard = nl > 0
            pos1 = (seg0 + g) % self.S
            size1 = ring_write_size(state.size, seg0 * self.B,
                                    g * self.B, self.capacity)
        if per_shard:
            tidx = (seg0[:, None] * self.B
                    + jnp.arange(g * self.B, dtype=jnp.int32)[None])
        else:
            tidx = seg0 * self.B + jnp.arange(g * self.B, dtype=jnp.int32)
        rows = items["seg_frames"].astype(self.obs_dtype) \
            .reshape(*lead, g * self.F, self.frame_bytes)
        if self.frame_row != self.frame_bytes:
            rows = jnp.pad(rows, [(0, 0)] * (nl + 1)
                           + [(0, self.frame_row - self.frame_bytes)])
        # pack before the ring write: dus_rows' own astype converts
        # VALUES and would store one pixel per word
        rows = as_words(rows)
        storage = dict(state.storage)
        if per_shard:
            storage["frames"] = dus_rows_per_shard(
                state.storage["frames"], rows, seg0 * self.F)
            for k in ("action", "reward", "discount", "next_off"):
                storage[k] = dus_rows_per_shard(
                    state.storage[k],
                    items[k].reshape(*lead, g * self.B), seg0 * self.B)
        else:
            storage["frames"] = dus_rows(state.storage["frames"], rows,
                                         seg0 * self.F, lead=nl)
            for k in ("action", "reward", "discount", "next_off"):
                storage[k] = dus_rows(state.storage[k],
                                      items[k].reshape(*lead, g * self.B),
                                      seg0 * self.B, lead=nl)
        valid = items["next_off"].reshape(*lead, g * self.B) > 0
        pri = jnp.where(
            valid,
            (td_abs.reshape(*lead, g * self.B) + self.eps) ** self.alpha,
            0.0)
        tree, pos, size = ring_finish(state.tree, tidx, pri, pos1, size1,
                                      lead)
        return ReplayState(storage=storage, tree=tree, pos=pos, size=size)

    def add(self, state: ReplayState, items: Any,
            td_abs: jax.Array) -> ReplayState:
        """Write G whole segments at the segment cursor.

        items: {"seg_frames": [G, F, H, W], "action"/"reward"/"discount"/
        "next_off": [G, B]}; td_abs: [G, B] initial |TD| (0 on dead pads).
        In-place block write with skip-to-head wrap (_write_segments).
        """
        return self._write_segments(state, items, td_abs, lead=())

    def add_lockstep(self, state: ReplayState, items: Any,
                     td_abs: jax.Array) -> ReplayState:
        """Segment add for [dp, ...]-stacked lockstep shard states —
        see PrioritizedReplay.add_lockstep for the lockstep-cursor
        contract. items: {"seg_frames": [dp, G, F, H, W], fields:
        [dp, G, B]}; td_abs: [dp, G, B]."""
        return self._write_segments(state, items, td_abs,
                                    lead=(td_abs.shape[0],))

    # -- tiered cold store hooks (segment units; see PrioritizedReplay) ----

    def evict_plan(self, state: ReplayState, block: int) -> jax.Array:
        """Start SEGMENT of the minimum-priority-mass run of `block`
        contiguous segments (eviction granularity is whole segments —
        the transition<->frame aliasing invariant demands it)."""
        seg_mass = state.tree[self.capacity:].reshape(self.S, self.B) \
            .sum(axis=-1)
        c = jnp.concatenate([jnp.zeros(1, seg_mass.dtype),
                             jnp.cumsum(seg_mass)])
        return jnp.argmin(c[block:] - c[:-block]).astype(jnp.int32)

    def read_region(self, state: ReplayState, seg0: jax.Array,
                    block: int) -> tuple[Any, jax.Array]:
        """-> (staging-layout segments {"seg_frames": [g, F, H, W],
        fields [g, B]}, stored leaf priorities [g, B]) for the `block`
        segments at seg0 — the exact shape _write_segments consumes, so
        a cold round trip restages bit-identically."""
        g = block
        st = state.storage
        rows = as_bytes(jax.lax.dynamic_slice_in_dim(
            st["frames"], seg0 * self.F, g * self.F))
        items = {"seg_frames": rows[:, :self.frame_bytes].reshape(
            g, self.F, self.h, self.w)}
        for k in ("action", "reward", "discount", "next_off"):
            items[k] = jax.lax.dynamic_slice_in_dim(
                st[k], seg0 * self.B, g * self.B).reshape(g, self.B)
        pri = jax.lax.dynamic_slice_in_dim(
            state.tree, self.capacity + seg0 * self.B,
            g * self.B).reshape(g, self.B)
        return items, pri

    def add_at(self, state: ReplayState, items: Any, td_abs: jax.Array,
               seg0: jax.Array) -> ReplayState:
        """Directed segment add: overwrite the G segments at seg0 (an
        evict_plan result) instead of the FIFO segment cursor."""
        return self._write_segments(state, items, td_abs, lead=(),
                                    seg0=seg0)

    def add_at_lockstep(self, state: ReplayState, items: Any,
                        td_abs: jax.Array,
                        seg0: jax.Array) -> ReplayState:
        """Directed segment add for [dp, ...]-stacked shard states:
        shard d gets items[d] at segment seg0[d] (its own evict_plan
        result). Per-shard unrolled DUS writes (dus_rows_per_shard);
        shard cursors diverge, which is safe because the eviction swap
        only runs on a full ring — the lockstep FIFO cursor is never
        consulted again (see PrioritizedReplay.add_at_lockstep)."""
        return self._write_segments(state, items, td_abs,
                                    lead=(td_abs.shape[0],), seg0=seg0)

    def _gather(self, state: ReplayState, idx: jax.Array,
                chunks: int = 1) -> dict:
        """Reconstruct flat transitions {obs, action, reward, next_obs,
        discount} for transition indices idx [Bt] — a row gather of
        stack frames per side, then a batch-local relayout to
        [Bt, H, W, stack] uint8 (the ring itself is never relaid out).

        The gather fetches whole rows of words, [stack, B, words]: one
        7 kB read per sampled frame (25 ns a row on the v5e; the same
        bytes as uint8 rows cost 86, four rows fetched per row
        returned — module docstring). What follows is chosen for the
        one relayout XLA cannot avoid: the ring is pixel-minor and the
        first conv reads the batch in the lanes with the stack beside
        it, i.e. 32-bit words [H*W, B] whose four bytes are the four
        frames' values of one pixel. A stack of four is therefore
        built on words (`packing.stacks_of_four`): a 4x4 byte
        transpose across the four frames' words, each result transposed
        rows x words as a 32-bit 2-D transpose, the four pixel phases
        interleaved, and ONE bitcast to uint8 — on the chip a single
        fusion from
        the gathered words to conv1's operand layout. Of the forms
        timed in the real step this was the fastest (PERF.md §6,
        PR 29); turning the words back into bytes first and
        transposing those — the plain form any other stack depth
        takes — gives back most of what the gather saves.

        PR 25's two rules stand. The stack axis is FIRST in the index,
        so the rows of one frame position arrive together. `chunks`=K
        (idx chunk-major, the K-batch cycle) gathers each chunk's B
        rows on its own: chunk j of the result is then a whole array,
        not a slice of the lane dimension of a K*B one, and the K SGD
        steps read it as it lands. The bytes returned are the same for
        every `chunks`."""
        st = state.storage
        seg, j = idx // self.B, idx % self.B
        base = seg * self.F + j
        offs = jnp.arange(self.stack, dtype=jnp.int32)[:, None]

        def stack_of(rows_base):
            with jax.named_scope("replay.sample_gather"):
                f = st["frames"][offs + rows_base[None, :]]  # [st,B,words]
            if self.stack != 4:      # plain form: bytes, then transpose
                f = as_bytes(f)[..., :self.frame_bytes].reshape(
                    self.stack, -1, self.h, self.w)
                return jnp.transpose(f, (1, 2, 3, 0))    # -> [B,H,W,st]
            obs = stacks_of_four(f, self.frame_bytes)    # [H*W,B,st]
            return obs.reshape(self.h, self.w, -1, self.stack) \
                .transpose(2, 0, 1, 3)                   # -> [B,H,W,st]

        def stack_at(rows_base):
            return jnp.concatenate(
                [stack_of(r) for r in jnp.split(rows_base, chunks)])

        return {
            "obs": stack_at(base),
            "action": st["action"][idx],
            "reward": st["reward"][idx],
            # dead slots: next_off 0 — never sampled
            "next_obs": stack_at(base + st["next_off"][idx]),
            "discount": st["discount"][idx],
        }

    def sample_items(self, state: ReplayState, rng: jax.Array, batch: int,
                     chunks: int = 1
                     ) -> tuple[Any, jax.Array, jax.Array]:
        """-> (flat transition batch, leaf indices [B], probs [B]);
        `chunks` as in PrioritizedReplay.sample_items."""
        idx, probs = sum_tree.sample(state.tree, rng, batch,
                                     size=state.size, chunks=chunks)
        return self._gather(state, idx, chunks), idx, probs

    # sample() is inherited: PrioritizedReplay.sample composes
    # sample_items (overridden above) with IS weights and the
    # valid_mask dead-slot zeroing (overridden below).

    def update_priorities(self, state: ReplayState, idx: jax.Array,
                          td_abs: jax.Array) -> ReplayState:
        pri = (td_abs + self.eps) ** self.alpha
        # a dead slot must stay dead: a clamp-landed draw would otherwise
        # write (garbage-TD)^alpha here and resurrect it into the
        # sampling distribution permanently
        pri = jnp.where(state.storage["next_off"][idx] > 0, pri, 0.0)
        return state._replace(tree=sum_tree.update(state.tree, idx, pri))

    def valid_mask(self, state: ReplayState, idx: jax.Array) -> jax.Array:
        """[B] f32: 1 on live transitions, 0 on dead pad slots."""
        return (state.storage["next_off"][idx] > 0).astype(jnp.float32)

    def cursor_transitions(self, state: ReplayState) -> jax.Array:
        """Write cursor in transition units (the frame ring's `pos`
        counts segments) — the learning-health age statistic's clock."""
        return state.pos * self.B

    def live_transitions(self, state: ReplayState) -> jax.Array:
        """Count of live (non-pad) transition slots, reducing only the
        trailing slot axis — so it works unchanged on a single-chip
        state (scalar out) and on the dp-sharded lockstep state
        ([dp] out), where it feeds the per-shard fill stats of
        `DistLearner.shard_stats`."""
        return (state.storage["next_off"] > 0).sum(axis=-1)
