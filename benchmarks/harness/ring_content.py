"""What the seed writes into the replay ring: every byte and field is
a pure function of (seed, global segment id, position), so the fill
can be generated on the device in bulk and any sampled transition can
be recomputed on the host afterwards and compared byte for byte.

`xp` is `numpy` or `jax.numpy`: one definition serves the device-side
fill (jnp, inside a jit) and the host-side check (numpy).
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Geometry(NamedTuple):
    """Frame-ring segment geometry (replay/frame_ring.py layout)."""
    seg: int        # transitions per segment (B)
    frames: int     # frames per segment (F = B + n_step + stack - 1)
    height: int
    width: int
    stack: int
    n_step: int
    num_actions: int
    gamma: float


def geometry(cfg, spec) -> Geometry:
    h, w, stack = spec.obs_shape
    b, n = cfg.replay.seg_transitions, cfg.learner.n_step
    return Geometry(b, b + n + stack - 1, h, w, stack, n,
                    spec.num_actions, cfg.learner.gamma)


class Content(NamedTuple):
    """Everything the ring's contents are a function of."""
    geom: Geometry
    seed: int
    sigma: float            # log-normal spread of the initial |TD|
    terminal_one_in: int    # one transition in this many is terminal


def _mix(xp, a, b, seed: int, salt: int):
    """32-bit integer hash of (a, b, seed, salt) -> uint32, the same
    bits under numpy and jax.numpy (wrapping uint32 arithmetic)."""
    u = xp.uint32
    h = (a.astype(u) * u(0x9E3779B1) + b.astype(u) * u(0x85EBCA77)
         + u((seed * 0xC2B2AE3D + salt * 0x27D4EB2F) & 0xFFFFFFFF))
    h = h ^ (h >> u(15))
    h = h * u(0x2C1B3C6D)
    h = h ^ (h >> u(12))
    h = h * u(0x297A2D39)
    return h ^ (h >> u(15))


def _unit(xp, h):
    """uint32 -> float32 in (0, 1)."""
    return ((h >> xp.uint32(8)).astype(xp.float32) + 0.5) / 16777216.0


def frame_rows(xp, c: Content, rows):
    """rows: integer array [...] of global frame-row ids (segment *
    frames + frame) -> uint8 [..., H, W]."""
    g = c.geom
    pix = xp.arange(g.height * g.width, dtype=xp.uint32)
    h = _mix(xp, rows[..., None], pix, c.seed, 1)
    return (h >> xp.uint32(24)).astype(xp.uint8).reshape(
        *rows.shape, g.height, g.width)


def fields(xp, c: Content, trans) -> dict:
    """trans: integer array [...] of global transition ids (segment *
    seg + slot) -> the per-transition fields and initial |TD|."""
    g, seed = c.geom, c.seed
    zero = xp.zeros_like(trans)
    action = (_mix(xp, trans, zero, seed, 2)
              % xp.uint32(g.num_actions)).astype(xp.int32)
    # clipped rewards summed over n steps land on small integers
    reward = (_mix(xp, trans, zero, seed, 3) % xp.uint32(3)
              ).astype(xp.float32) - 1.0
    terminal = (_mix(xp, trans, zero, seed, 4)
                % xp.uint32(c.terminal_one_in)) == 0
    discount = xp.where(terminal, 0.0, g.gamma ** g.n_step
                        ).astype(xp.float32)
    # every slot is live: next_off = n_step (dead pads are an actor's
    # episode-tail artefact, under 1% of a real ring)
    next_off = xp.full(trans.shape, g.n_step, dtype=xp.int32)
    # log-normal initial |TD| (Box-Muller on two hashes), so the
    # sum-tree is not flat and the descent takes uneven paths
    u1 = _unit(xp, _mix(xp, trans, zero, seed, 5))
    u2 = _unit(xp, _mix(xp, trans, zero, seed, 6))
    z = xp.sqrt(-2.0 * xp.log(u1)) * xp.cos(2.0 * np.pi * u2)
    td_abs = (0.1 * xp.exp(c.sigma * z)).astype(xp.float32)
    return {"action": action, "reward": reward, "discount": discount,
            "next_off": next_off, "priorities": td_abs}


def segments(xp, c: Content, seg_ids) -> dict:
    """seg_ids [...] -> one staged block in the layout `learner.add`
    takes: seg_frames [..., F, H, W] u8, fields and priorities
    [..., B]."""
    g = c.geom
    rows = seg_ids[..., None] * g.frames + xp.arange(
        g.frames, dtype=seg_ids.dtype)
    trans = seg_ids[..., None] * g.seg + xp.arange(
        g.seg, dtype=seg_ids.dtype)
    out = fields(xp, c, trans)
    out["seg_frames"] = frame_rows(xp, c, rows)
    return out


def expected_transitions(c: Content, seg_ids: np.ndarray,
                         slots: np.ndarray) -> dict:
    """Host recomputation of the flat transitions the ring must return
    for (global segment id, slot) pairs: obs/next_obs [n, H, W, stack]
    u8 plus action/reward/discount."""
    g = c.geom
    trans = seg_ids.astype(np.int64) * g.seg + slots
    f = fields(np, c, trans)
    base = seg_ids.astype(np.int64) * g.frames + slots
    offs = np.arange(g.stack)

    def stack_at(first_row):
        planes = frame_rows(np, c, first_row[:, None] + offs)
        return np.moveaxis(planes, 1, -1)

    return {"obs": stack_at(base),
            "next_obs": stack_at(base + f["next_off"]),
            "action": f["action"], "reward": f["reward"],
            "discount": f["discount"]}
