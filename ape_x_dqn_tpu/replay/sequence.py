"""Sequence replay: host-side sequence assembly + device storage.

Actors assemble fixed-length overlapping sequences; the sequences are
then items in the generic device-resident PrioritizedReplay, so
sampling/priority updates run inside the learner jit exactly like flat
transitions.

What state a sequence carries is its family's (runtime/family.py
`ACTOR_STATE`), and there are two kinds. R2D2 stores the recurrent
state from *before* the first step (SURVEY.md §2.2 "Sequence replay",
§3.4): `init_c`, `init_h`. The token-level decoder family stores none:
its item is obs (one int32 id per step), actions, rewards, terminals
and mask, nothing else — a replayed window starts from an empty
attention cache and the burn-in prefix rebuilds its context as a latent
cache inside the learner jit. `sequence_item_spec` and
`SequenceBuilder` take the stored entries by name; no entry is stored
by habit, and none is a zero-width row in the packed store.

Defaults follow Kapturowski et al. 2019: length 80, overlap 40
(adjacent sequences share half their steps), burn-in 40 handled by the
loss, priority = eta*max|td| + (1-eta)*mean|td|.
"""

from __future__ import annotations

from typing import Any

import numpy as np

from ape_x_dqn_tpu.replay.packing import (WORDS, frame_mode,
                                          stacks_of_four)


# THE predicate for single-frame sequence storage — an alias of the
# ONE shared implementation in replay/packing.py (frame_ring_mode in
# replay/frame_ring.py is the same object), so layout selection
# (runtime/family.py) and budget pricing (utils/hbm.py) cannot drift
# from each other or from the flat-DQN segment layout.
sequence_frame_mode = frame_mode


def sequence_item_spec(obs_shape: tuple[int, ...], obs_dtype,
                       seq_len: int, state: int | dict,
                       frame_mode: bool = False) -> dict:
    """ShapeDtypeStruct-style pytree describing ONE stored sequence.

    `state`: {name: shape} of the float32 state entries stored with a
    sequence, each as `init_<name>` (family.stored_state_spec; {} for a
    family that stores none); an int n is the LSTM's
    {"c": (n,), "h": (n,)}.

    frame_mode (pixel obs only): store single frames
    [seq_len + stack - 1, H, W] instead of per-step stacks
    [seq_len, H, W, stack] — consecutive steps share all but one frame,
    so stacked storage is ~stack x redundant (~4x at Atari shapes; the
    attested 100k-sequence capacity only fits in HBM without it).
    Stacks are rebuilt by `batch_to_sequence_batch` inside the learner
    jit, once per SGD step. Not free: as four `stack`ed slices the
    rebuild was 16.8% of an R2D2 step on the v5e (PERF.md §5, PR 26).
    """
    import jax
    f32 = np.float32
    if frame_mode:
        h, w, stack = obs_shape
        obs_sds = jax.ShapeDtypeStruct((seq_len + stack - 1, h, w),
                                       obs_dtype)
        obs_key = "seq_frames"
    else:
        obs_sds = jax.ShapeDtypeStruct((seq_len, *obs_shape), obs_dtype)
        obs_key = "obs"
    if isinstance(state, int):
        state = {"c": (state,), "h": (state,)}
    return {
        obs_key: obs_sds,
        "actions": jax.ShapeDtypeStruct((seq_len,), np.int32),
        "rewards": jax.ShapeDtypeStruct((seq_len,), f32),
        "terminals": jax.ShapeDtypeStruct((seq_len,), f32),
        "mask": jax.ShapeDtypeStruct((seq_len,), f32),
        **{"init_" + name: jax.ShapeDtypeStruct(tuple(shape), f32)
           for name, shape in state.items()},
    }


class SequenceBuilder:
    """Per-env accumulator emitting overlapping fixed-length sequences.

    Actors attach a per-step |TD| estimate (1-step, from the Q-values they
    already hold for action selection), and every emitted item carries an
    initial sequence priority under the extra key ``"priority"`` — the
    same eta-mix of max/mean |TD| the learner writes back (SURVEY.md §2.2
    "Actor runtime": initial priorities computed actor-side). Callers
    strip the key before device storage via `split_priorities`.
    """

    def __init__(self, seq_len: int = 80, overlap: int = 40,
                 lstm_size: int = 512, priority_eta: float = 0.9,
                 frame_mode: bool = False,
                 state_keys: tuple[str, ...] = ("c", "h")):
        """state_keys: the entries of `append`'s pre_state, in order,
        that an emitted item stores as `init_<key>` (the LSTM's by
        default; () for a family that stores no state).

        frame_mode: emit single frames ("seq_frames") instead of
        per-step stacks — valid for [H, W, stack] pixel obs whose
        channels slide one frame per step (the Atari wrapper's
        invariant; holds within an episode, and sequences never span
        episodes)."""
        assert 0 <= overlap < seq_len
        self.seq_len = seq_len
        self.overlap = overlap
        self.lstm_size = lstm_size
        self.priority_eta = priority_eta
        self.frame_mode = frame_mode
        self.state_keys = tuple(state_keys)
        self._steps: list[dict] = []  # each: obs/action/reward/terminal/pre
        self._retained = 0  # leading steps already covered by a prior emit

    def append(self, obs, action, reward, terminal: bool,
               pre_state: tuple[np.ndarray, ...],
               td: float = 0.0,
               episode_end: bool | None = None) -> list[dict]:
        """Add one step; pre_state is the state fed to the net AT this
        step, one array per `state_keys` entry ((c, h) for the LSTM).

        `terminal` marks a bootstrapping-relevant episode end (stored in
        the terminals array); `episode_end` (default: terminal) flushes
        the sequence — a time-limit truncation ends the sequence without
        marking a terminal, since the recurrent state resets but the
        bootstrap must survive. Returns 0+ completed sequence items
        (dicts matching sequence_item_spec plus "priority").
        """
        if episode_end is None:
            episode_end = terminal
        self._steps.append(dict(
            obs=np.asarray(obs), action=int(action), reward=float(reward),
            terminal=bool(terminal), td=abs(float(td)),
            pre=tuple(np.asarray(x, np.float32).reshape(-1)
                      for x in pre_state[:len(self.state_keys)])))
        out = []
        if len(self._steps) == self.seq_len:
            out.append(self._emit(self._steps))
            # retain the trailing overlap as the head of the next sequence
            self._steps = self._steps[self.seq_len - self.overlap:] \
                if self.overlap else []
            self._retained = len(self._steps)
        if episode_end:
            # flush the padded partial tail, but only if it contains steps
            # not already covered by the previous emit's overlap
            if len(self._steps) > self._retained:
                out.append(self._emit(self._steps))
            self._steps = []
            self._retained = 0
        return out

    def reset(self) -> None:
        self._steps = []
        self._retained = 0

    def flush(self) -> list[dict]:
        """Emit the padded partial tail (actor shutdown), if it holds any
        step not already covered by the previous emit's overlap."""
        out = []
        if len(self._steps) > self._retained:
            out.append(self._emit(self._steps))
        self._steps = []
        self._retained = 0
        return out

    def _emit(self, steps: list[dict]) -> dict:
        n = len(steps)
        assert n > 0
        length = self.seq_len
        first = steps[0]
        actions = np.zeros(length, np.int32)
        rewards = np.zeros(length, np.float32)
        terminals = np.zeros(length, np.float32)
        mask = np.zeros(length, np.float32)
        tds = np.zeros(n, np.float32)
        for i, s in enumerate(steps):
            actions[i] = s["action"]
            rewards[i] = s["reward"]
            terminals[i] = float(s["terminal"])
            mask[i] = 1.0
            tds[i] = s["td"]
        eta = self.priority_eta
        priority = eta * float(tds.max()) + (1 - eta) * float(tds.mean())
        item = {
            "actions": actions, "rewards": rewards,
            "terminals": terminals, "mask": mask,
            **{"init_" + k: x
               for k, x in zip(self.state_keys, first["pre"])},
            "priority": priority,
        }
        if self.frame_mode:
            # single frames: [0:stack] = the first step's channels, then
            # one new frame (newest channel) per step; obs stack at step
            # i is frames[i:i+stack] by the sliding invariant. Pad the
            # unmasked tail by repeating the last frame.
            h, w, stack = first["obs"].shape
            frames = np.zeros((length + stack - 1, h, w),
                              first["obs"].dtype)
            for c in range(stack):
                frames[c] = first["obs"][..., c]
            for i, s in enumerate(steps[1:], start=1):
                frames[stack - 1 + i] = s["obs"][..., -1]
            frames[stack - 1 + n:] = frames[stack - 2 + n]
            item["seq_frames"] = frames
        else:
            obs = np.zeros((length, *first["obs"].shape),
                           first["obs"].dtype)
            for i, s in enumerate(steps):
                obs[i] = s["obs"]
            item["obs"] = obs
        return item


def split_priorities(items: list[dict]) -> tuple[list[dict], np.ndarray]:
    """Strip the builder's "priority" key -> (storage items, priorities)."""
    pris = np.asarray([it.get("priority", 0.0) for it in items], np.float32)
    return [{k: v for k, v in it.items() if k != "priority"}
            for it in items], pris


def stack_items(items: list[dict]) -> dict:
    """Stack a list of sequence items into a batch pytree of [B, ...].

    Skips the builder's scalar "priority" side-channel key, which is not
    part of the stored item spec.
    """
    return {k: np.stack([it[k] for it in items])
            for k in items[0] if k != "priority"}


def _stacks(frames, words, start: int, stop: int, stack: int, prepare):
    """Single frames [B, n, H, W] -> what conv1 reads for steps
    start..stop, [B, stop - start, H, W, stack]: channel c of step t is
    frame t + c, passed once through `prepare`.

    `words`: the same frames as the packed store gathered them,
    uint32 [B, n, row] with word k of a row holding pixels 4k..4k+3 of
    one frame (replay/packing.py), or None. With them a stack of four
    is built on whole words, as `FrameRingReplay._gather` builds its
    own (`packing.stacks_of_four`): step t's four rows t..t+3 go
    through a 4x4 byte transpose (four words of four adjacent pixels of
    four consecutive frames -> four words, each one pixel's stack), each
    result is transposed [B*T, row] -> [row, B*T] as a 32-bit 2-D
    transpose, the four pixel phases are interleaved, and ONE bitcast
    gives [H*W, B*T, 4] uint8 in the order conv1 reads (H, W, then
    batch x time in the lanes with the stack beside it) — the order
    `prepare` sees and materialises. `frames` then gives its shape and
    nothing else, and its bytes are never made. On the v5e the
    compiled step then holds, a side of the cut: one fusion over the
    four slices (the byte transpose, four outputs), the four 2-D
    transposes as layout changes of those outputs and not as copies,
    one fusion that interleaves the phases and cuts the row's pad, one
    copy and one reshape of the words to [H, W, B*T] (the one pass
    left that moves data without computing: 84 is not a multiple of
    the 8-sublane tile), and one unpack-and-scale to the compute dtype
    straight into conv1's operand layout. With the gather that feeds
    it (replay/packing.py::gather_rows) r2d2_offline's step went 7.72
    -> 5.99 ms (PERF.md §6, PR 42); the same words turned back into
    bytes and `stack`ed read 10.78.

    Any other dtype or depth, or frames that came as bytes alone,
    take the plain form: four `stack`ed slices on the last axis, which
    on the v5e cost a concatenate that writes at a tenth of HBM speed
    and two relayout copies of the 4x larger stacks (PERF.md §6,
    PR 27)."""
    import jax.numpy as jnp

    bsz, _, h, w = frames.shape
    steps = stop - start
    if words is not None and stack == 4:
        obs = stacks_of_four(
            [words[:, start + c:stop + c].reshape(bsz * steps, -1)
             for c in range(stack)], h * w)              # [H*W, B*T, 4]
        obs = prepare(obs.reshape(h, w, bsz * steps, stack))
        return obs.transpose(2, 0, 1, 3).reshape(bsz, steps, h, w, stack)
    return prepare(jnp.stack(
        [frames[:, start + c:stop + c] for c in range(stack)], axis=-1))


def batch_to_sequence_batch(items: Any, compute_dtype=None,
                            burn_in: int = 0):
    """Device item batch (dict of [B, L, ...]) -> losses.SequenceBatch.

    With `compute_dtype` (the net's; the learners pass it) `obs` is
    what conv1 reads, prepared ONCE per SGD step: stacked, scaled as
    `models.base.preprocess_obs` scales (which passes a float input
    through, so the net needs no second entry point) and materialised
    behind an optimization barrier, so that the four net applications
    of the R2D2 loss (online/target x burn-in/trained steps) read
    time-slices of one array and XLA neither repeats the preparation
    per application nor folds the convert into each conv's operand
    read (measured slower). `burn_in` is where the loss will cut the
    time axis: the two sides are prepared as arrays of their own and
    joined, and XLA hands each slice the array it was cut from; any
    other cut is still right, only slower. Without `compute_dtype` the
    stored dtype is kept (uint8 stacks, scaled by the net).

    Frame-mode items carry "seq_frames" [B, L+stack-1, H, W] — and,
    sampled from the packed store, the word rows those frames were
    gathered as beside them (replay/packing.py::WORDS) — and the
    per-step stacks are rebuilt here (`_stacks`); per-step storage
    ("obs") has nothing to rebuild. Measured 16.8% of an R2D2 step
    before PR 27, with the scale and relayout behind it 32.7%, so
    everything between the decoded frames and conv1's operand sits
    under the one scope `r2d2.stack_rebuild`."""
    import jax
    import jax.numpy as jnp

    from ape_x_dqn_tpu.models.base import preprocess_obs
    from ape_x_dqn_tpu.ops.losses import SequenceBatch

    def prepare(x):
        if compute_dtype is None:
            return x
        return jax.lax.optimization_barrier(
            preprocess_obs(x, compute_dtype))

    length = items["actions"].shape[-1]
    cuts = (0, burn_in, length) if 0 < burn_in < length else (0, length)
    spans = list(zip(cuts, cuts[1:]))
    with jax.named_scope("r2d2.stack_rebuild"):
        if "seq_frames" in items:
            f = items["seq_frames"]
            stack = f.shape[1] - length + 1
            words = items.get("seq_frames" + WORDS)
            parts = [_stacks(f, words, a, b, stack, prepare)
                     for a, b in spans]
        else:
            parts = [prepare(items["obs"][:, a:b]) for a, b in spans]
        obs = parts[0] if len(parts) == 1 else jnp.concatenate(parts,
                                                               axis=1)
    return SequenceBatch(
        obs=obs, actions=items["actions"],
        rewards=items["rewards"], terminals=items["terminals"],
        mask=items["mask"],
        init_state=(items["init_c"], items["init_h"]))
