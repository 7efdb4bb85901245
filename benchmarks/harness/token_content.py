"""What the seed writes into a token-sequence replay (the decoder
family's items): every id, reward, terminal and mask of a stored
sequence is a pure function of (seed, global sequence id), so the fill
is generated on the device in bulk and any sampled sequence is
recomputed on the host and compared byte for byte. `xp` is `numpy` or
`jax.numpy`, as in ring_content.py, whose hash this file uses; every
field but the initial priority is integer arithmetic, so the two agree
to the bit.

One item is what `replay/sequence.sequence_item_spec(..., state={})`
describes: `obs` [L] int32 (one token id per step), `actions` [L] int32
(the next token: `actions[t]` is the id at position t + 1, as an
agent's action is the token it emits next), `rewards`/`terminals`/
`mask` [L] float32 — and no state entry. The shapes of real traffic
that the loss and the expert layers branch on are there:

- ids follow a Zipf law with exponent `token_zipf_exponent` over the V
  ids held (log-uniform by octaves: an octave k is drawn with weight
  2^(k (1 - s)), then an id uniformly inside [2^k, 2^(k+1)), folded
  modulo V), so a few ids are frequent, repeated ids route alike and
  the held experts' load is uneven, as text makes it;
- one sequence in `episode_tail_one_in` is an episode's tail: its valid
  length is uniform in [burn_in + 1, L - 1], its last valid step is the
  episode's terminal and the rest is padding (mask 0, fields 0);
- one valid step in `terminal_one_in` is a terminal inside a sequence;
- one valid step in `reward_one_in`, and every terminal, carries a
  reward of +1 or -1; the rest 0 (sparse feedback);
- initial priorities are log-normal, so the sum-tree is not flat.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from benchmarks.harness.ring_content import _mix, _unit


class Content(NamedTuple):
    seq_len: int               # L, stored steps per sequence
    burn_in: int
    vocab: int                 # V, ids held
    seed: int
    sigma: float               # log-normal spread of the initial priority
    terminal_one_in: int       # valid steps per mid-sequence terminal
    episode_tail_one_in: int   # sequences per episode tail
    reward_one_in: int         # valid steps per rewarded step
    octave_thresholds: tuple   # uint32 cumulative weights of the octaves


def _octaves(vocab: int, exponent: float) -> tuple:
    """Cumulative weights of the octaves [2^k, 2^(k+1)), k = 0 ..
    ceil(log2 V) - 1, scaled to 2^32: octave k holds ranks whose
    probability under p(r) ~ r^-s sums to about 2^(k (1 - s))."""
    n = max(int(np.ceil(np.log2(vocab))), 1)
    w = np.power(2.0, np.arange(n) * (1.0 - float(exponent)))
    cum = np.cumsum(w) / w.sum()
    return tuple(int(min(c * 2 ** 32, 2 ** 32 - 1)) for c in cum)


def content(cfg, spec, seed: int, params: dict) -> Content:
    """`params` is the traffic mix (benchmarks/traffic/<mix>.json)."""
    return Content(cfg.replay.seq_length, cfg.replay.burn_in,
                   int(spec.num_actions), int(seed),
                   float(params["priority_lognormal_sigma"]),
                   int(params["terminal_one_in"]),
                   int(params["episode_tail_one_in"]),
                   int(params["reward_one_in"]),
                   _octaves(spec.num_actions,
                            params["token_zipf_exponent"]))


def token_ids(xp, c: Content, positions):
    """Global token positions [...] (any integer dtype) -> int32 ids."""
    u = xp.uint32
    zero = xp.zeros_like(positions)
    pick = _mix(xp, positions, zero, c.seed, 21)
    octave = xp.zeros(positions.shape, xp.uint32)
    for t in c.octave_thresholds[:-1]:
        octave = octave + (pick > u(t)).astype(xp.uint32)
    inside = _mix(xp, positions, zero, c.seed, 22)
    low = u(1) << octave
    rank = low + (inside & (low - u(1)))              # 1 .. 2^n - 1
    return ((rank - u(1)) % u(c.vocab)).astype(xp.int32)


def valid_length(xp, c: Content, seq_ids):
    """[...] sequence ids -> int32 [...] valid steps (L, or an episode
    tail's burn_in + 1 .. L - 1)."""
    zero = xp.zeros_like(seq_ids)
    tail = (_mix(xp, seq_ids, zero, c.seed, 11)
            % xp.uint32(c.episode_tail_one_in)) == 0
    span = c.seq_len - 1 - c.burn_in
    short = (c.burn_in + 1 + (_mix(xp, seq_ids, zero, c.seed, 12)
                              % xp.uint32(span))).astype(xp.int32)
    return xp.where(tail, short, xp.int32(c.seq_len)).astype(xp.int32)


def sequences(xp, c: Content, seq_ids) -> dict:
    """seq_ids [...] -> the staged block `learner.add` takes (leaves
    [..., L]) plus `priorities` [...]."""
    u = xp.uint32
    zero = xp.zeros_like(seq_ids)
    t = xp.arange(c.seq_len, dtype=xp.int32)
    # a sequence owns L + 1 positions of the global token stream
    steps = seq_ids[..., None] * (c.seq_len + 1) + t.astype(seq_ids.dtype)
    szero = xp.zeros_like(steps)
    n_valid = valid_length(xp, c, seq_ids)
    mask = t < n_valid[..., None]
    is_tail = n_valid < c.seq_len
    obs = xp.where(mask, token_ids(xp, c, steps), 0)
    actions = xp.where(mask, token_ids(xp, c, steps + 1), 0)
    mid = (_mix(xp, steps, szero, c.seed, 4) % u(c.terminal_one_in)) == 0
    episode_end = is_tail[..., None] & (t == n_valid[..., None] - 1)
    terminal = mask & (mid | episode_end)
    paid = terminal | (mask & ((_mix(xp, steps, szero, c.seed, 3)
                                % u(c.reward_one_in)) == 0))
    sign = 1.0 - 2.0 * (_mix(xp, steps, szero, c.seed, 5)
                        & u(1)).astype(xp.float32)
    rewards = xp.where(paid, sign, 0.0)

    u1 = _unit(xp, _mix(xp, seq_ids, zero, c.seed, 6))
    u2 = _unit(xp, _mix(xp, seq_ids, zero, c.seed, 7))
    z = xp.sqrt(-2.0 * xp.log(u1)) * xp.cos(2.0 * np.pi * u2)
    return {"obs": obs.astype(xp.int32), "actions": actions.astype(xp.int32),
            "rewards": rewards.astype(xp.float32),
            "terminals": terminal.astype(xp.float32),
            "mask": mask.astype(xp.float32),
            "priorities": (0.1 * xp.exp(c.sigma * z)).astype(xp.float32)}


ITEM_KEYS = ("obs", "actions", "rewards", "terminals", "mask")
