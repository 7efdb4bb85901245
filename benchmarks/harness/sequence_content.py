"""What the seed writes into a sequence replay (R2D2 items): every
byte and field of a stored sequence is a pure function of (seed,
global sequence id), so the fill is generated on the device in bulk
and any sampled sequence is recomputed on the host and compared byte
for byte. `xp` is `numpy` or `jax.numpy`, as in ring_content.py, whose
hash this file uses.

One item is what `replay/sequence.sequence_item_spec(frame_mode=True)`
describes and `SequenceBuilder._emit` ships: `seq_frames` [L + stack -
1, H, W] u8, `actions`/`rewards`/`terminals`/`mask` [L], `init_c`/
`init_h` [lstm]. The shapes of real traffic that the loss branches on
are there:

- one sequence in `episode_tail_one_in` is an episode's tail: its valid
  length is uniform in [burn_in + 1, L - 1], its last valid step is the
  episode's terminal and the rest is padding (mask 0, fields 0), as the
  builder pads;
- one valid step in `terminal_one_in` is a terminal inside a sequence
  (a lost life: the bootstrap is cut, the sequence goes on);
- the stored recurrent state is uniform in +-`init_state_scale`, not
  zeros (only an episode's first sequence starts from zeros);
- initial priorities are log-normal, so the sum-tree is not flat.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from benchmarks.harness.ring_content import _mix, _unit


class Geometry(NamedTuple):
    seq_len: int     # L, stored steps per sequence
    burn_in: int
    frames: int      # L + stack - 1
    height: int
    width: int
    stack: int
    lstm: int
    num_actions: int


def geometry(cfg, spec) -> Geometry:
    h, w, stack = spec.obs_shape
    length = cfg.replay.seq_length
    return Geometry(length, cfg.replay.burn_in, length + stack - 1, h, w,
                    stack, cfg.network.lstm_size, spec.num_actions)


class Content(NamedTuple):
    geom: Geometry
    seed: int
    sigma: float               # log-normal spread of the initial priority
    terminal_one_in: int       # valid steps per mid-sequence terminal
    episode_tail_one_in: int   # sequences per episode tail
    init_state_scale: float


def content(cfg, spec, seed: int, params: dict) -> Content:
    """`params` is the traffic mix (benchmarks/traffic/<mix>.json)."""
    return Content(geometry(cfg, spec), int(seed),
                   float(params["priority_lognormal_sigma"]),
                   int(params["terminal_one_in"]),
                   int(params["episode_tail_one_in"]),
                   float(params["init_state_scale"]))


def valid_length(xp, c: Content, seq_ids):
    """[...] sequence ids -> int32 [...] valid steps (L, or an episode
    tail's burn_in + 1 .. L - 1)."""
    g = c.geom
    zero = xp.zeros_like(seq_ids)
    tail = (_mix(xp, seq_ids, zero, c.seed, 11)
            % xp.uint32(c.episode_tail_one_in)) == 0
    span = g.seq_len - 1 - g.burn_in          # 39 lengths at L=80, 40
    short = (g.burn_in + 1 + (_mix(xp, seq_ids, zero, c.seed, 12)
                              % xp.uint32(span))).astype(xp.int32)
    return xp.where(tail, short, xp.int32(g.seq_len)).astype(xp.int32)


def sequences(xp, c: Content, seq_ids) -> dict:
    """seq_ids [...] -> the staged block `learner.add` takes (leaves
    [..., *item shape]) plus `priorities` [...]."""
    g, seed = c.geom, c.seed
    u = xp.uint32
    zero = xp.zeros_like(seq_ids)
    rows = seq_ids[..., None] * g.frames + xp.arange(
        g.frames, dtype=seq_ids.dtype)
    pix = xp.arange(g.height * g.width, dtype=xp.uint32)
    frames = (_mix(xp, rows[..., None], pix, seed, 1) >> u(24)).astype(
        xp.uint8).reshape(*rows.shape, g.height, g.width)

    t = xp.arange(g.seq_len, dtype=xp.int32)
    steps = seq_ids[..., None] * g.seq_len + t.astype(seq_ids.dtype)
    szero = xp.zeros_like(steps)
    n_valid = valid_length(xp, c, seq_ids)
    mask = t < n_valid[..., None]
    is_tail = n_valid < g.seq_len
    actions = xp.where(mask, (_mix(xp, steps, szero, seed, 2)
                              % u(g.num_actions)).astype(xp.int32), 0)
    # clipped Atari rewards: mostly 0, +-1 on one step in eight each
    r = _mix(xp, steps, szero, seed, 3) % u(16)
    rewards = xp.where(mask, (r == 0).astype(xp.float32)
                       - (r == 1).astype(xp.float32), 0.0)
    lost_life = (_mix(xp, steps, szero, seed, 4)
                 % u(c.terminal_one_in)) == 0
    episode_end = is_tail[..., None] & (t == n_valid[..., None] - 1)
    terminals = (mask & (lost_life | episode_end)).astype(xp.float32)

    unit = xp.arange(g.lstm, dtype=xp.uint32)
    sid = seq_ids[..., None]

    def state(salt):
        return ((2.0 * _unit(xp, _mix(xp, sid, unit, seed, salt)) - 1.0)
                * c.init_state_scale).astype(xp.float32)

    u1 = _unit(xp, _mix(xp, seq_ids, zero, seed, 5))
    u2 = _unit(xp, _mix(xp, seq_ids, zero, seed, 6))
    z = xp.sqrt(-2.0 * xp.log(u1)) * xp.cos(2.0 * np.pi * u2)
    return {"seq_frames": frames, "actions": actions,
            "rewards": rewards.astype(xp.float32), "terminals": terminals,
            "mask": mask.astype(xp.float32),
            "init_c": state(7), "init_h": state(8),
            "priorities": (0.1 * xp.exp(c.sigma * z)).astype(xp.float32)}


ITEM_KEYS = ("seq_frames", "actions", "rewards", "terminals", "mask",
             "init_c", "init_h")
