"""dp-sharded frame-ring replay (ISSUE 9 tentpole (a)).

The dist driver has run frame-ring configs over the mesh since the
flagship e2e test; these tests pin the SEMANTICS of that path:

- dp=1 bitwise parity: the sharded state (leading [dp] axis, lockstep
  adds, vmapped single-shard sampling/write-back) at dp=1 must be the
  single-chip FrameRingReplay bit for bit — sharding is a layout
  decision, never a numerics decision.
- skewed-shard-fill IS weights: the global-N recipe from
  tests/test_parallel.py::test_skewed_shard_is_weights, re-proven on
  frame-ring storage where shard fills (not just priority masses) can
  diverge and dead episode-pad slots must train with weight 0.
- shard_stats: the per-shard fill/mass observability surface the
  dist driver publishes (obs/report.py's multichip section) and the
  run report consumes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.configs import LearnerConfig
from ape_x_dqn_tpu.parallel.dist_learner import DistLearner
from ape_x_dqn_tpu.parallel.mesh import make_mesh
from ape_x_dqn_tpu.replay.frame_ring import FrameRingReplay
from ape_x_dqn_tpu.runtime.family import dqn_family

OBS_SHAPE = (6, 6, 4)


def _ring(cap=64, seg=8, **kw):
    return FrameRingReplay(capacity=cap, seg_transitions=seg, n_step=3,
                           obs_shape=OBS_SHAPE, **kw)


def _segs(replay, g, rng, next_off=3):
    b, f = replay.B, replay.F
    items = {
        "seg_frames": jnp.asarray(
            rng.integers(0, 255, (g, f, *OBS_SHAPE[:2])), jnp.uint8),
        "action": jnp.asarray(rng.integers(0, 4, (g, b)), jnp.int32),
        "reward": jnp.asarray(rng.normal(size=(g, b)), jnp.float32),
        "discount": jnp.full((g, b), 0.97, jnp.float32),
        "next_off": jnp.full((g, b), next_off, jnp.int32),
    }
    pris = jnp.asarray(rng.uniform(0.1, 2.0, (g, b)), jnp.float32)
    return items, pris


def _stack1(tree):
    return jax.tree.map(lambda x: x[None], tree)


def _assert_state_eq(single, sharded_dp1):
    """Every sharded leaf is the single-chip leaf under a leading [1]."""
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)[0]), single, sharded_dp1)


# -- dp=1 bitwise parity ---------------------------------------------------


def test_dp1_lockstep_add_sample_update_parity():
    """add_lockstep / vmapped sample_items / vmapped update_priorities
    at dp=1 land the same bits as the single-chip ops under the same
    seed — storage, sum-tree, indices, probs, gathered stacks, all of
    it."""
    replay = _ring()
    rng = np.random.default_rng(0)
    items, pris = _segs(replay, 4, rng)

    s1 = replay.add(replay.init(), items, pris)
    sd = replay.add_lockstep(_stack1(replay.init()), _stack1(items),
                             pris[None])
    _assert_state_eq(s1, sd)

    # same key bits on both paths: split once, shard 0 IS the key
    keys = jax.random.split(jax.random.key(42), 1)
    it1, idx1, p1 = replay.sample_items(s1, keys[0], 16)
    itd, idxd, pd = jax.vmap(
        lambda rs, k: replay.sample_items(rs, k, 16))(sd, keys)
    np.testing.assert_array_equal(np.asarray(idx1), np.asarray(idxd)[0])
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(pd)[0])
    _assert_state_eq(it1, itd)

    td = jnp.asarray(np.random.default_rng(3).uniform(0.1, 1.0, 16),
                     jnp.float32)
    u1 = replay.update_priorities(s1, idx1, td)
    ud = jax.vmap(replay.update_priorities)(sd, idxd, td[None])
    _assert_state_eq(u1, ud)


def test_dp1_add_many_matches_single_chip_adds():
    """The dist learner's coalesced add_many ([g, dp, ...] unrolled
    lockstep chain) at dp=1 equals g sequential single-chip adds."""
    replay = _ring()
    mesh = make_mesh(dp=1, tp=1)
    lcfg = LearnerConfig(batch_size=16)
    learner = DistLearner(dqn_family(lambda p, o: o, lcfg), replay, lcfg, mesh)
    params = {"w": jnp.zeros((4,), jnp.float32)}
    state = learner.init(params, None, jax.random.key(0))

    rng = np.random.default_rng(7)
    blocks = [_segs(replay, 2, rng) for _ in range(3)]
    grp_items = jax.tree.map(lambda *xs: jnp.stack(xs)[:, None],
                             *[b[0] for b in blocks])
    grp_td = jnp.stack([b[1] for b in blocks])[:, None]
    state = learner.add_many(state, grp_items, grp_td)

    s1 = replay.init()
    for items, pris in blocks:
        s1 = replay.add(s1, items, pris)
    _assert_state_eq(s1, state.replay)


# -- skewed shard fills ----------------------------------------------------


def test_skewed_shard_fill_is_weights_frame_ring():
    """Frame-ring twin of test_parallel.py::test_skewed_shard_is_weights,
    with the skew in the FILL (shard 0 holds 2 segments, shard 1 is
    full) as well as the priority mass (1000x starved). Constant
    per-shard values + priorities make the beta=1 weighted estimate
    zero-variance, so one vmapped draw must recover the exact uniform
    mean over the GLOBAL live pool — the global-N recipe of
    _sample_weighted."""
    dp, cap, seg = 2, 64, 8
    replay = _ring(cap=cap, seg=seg, alpha=1.0, beta=1.0, eps=0.0)
    mesh = make_mesh(dp=dp, tp=1)
    lcfg = LearnerConfig(batch_size=64)
    learner = DistLearner(dqn_family(lambda p, o: o, lcfg), replay, lcfg, mesh)

    masses = [1e-3, 1.0]
    n_segs = [2, cap // seg]
    rng = np.random.default_rng(0)
    states = []
    for d in range(dp):
        g = n_segs[d]
        items, _ = _segs(replay, g, rng)
        # shard value g_d = d+1 rides the action field
        items["action"] = jnp.full((g, seg), d + 1, jnp.int32)
        live = g * seg
        pris = jnp.full((g, seg), masses[d] / live, jnp.float32)
        states.append(replay.add(replay.init(), items, pris))
    state = jax.tree.map(lambda *xs: jnp.stack(xs), *states)

    keys = jax.random.split(jax.random.key(0), dp)
    items, idx, w = learner._sample_weighted(state, keys, 32)
    w = np.asarray(w, np.float64)
    g_val = np.asarray(items["action"]).astype(np.float64)

    # all drawn slots are live, so every weight is positive and exactly
    # the valid_mask-gated formula weight
    valid = np.asarray(jax.vmap(replay.valid_mask)(state, idx))
    assert (valid == 1.0).all()
    assert (w > 0.0).all() and np.isfinite(w).all()

    n0, n1 = n_segs[0] * seg, n_segs[1] * seg
    uniform_mean = (n0 * 1.0 + n1 * 2.0) / (n0 + n1)
    est = float((w * g_val).mean())
    assert abs(est - uniform_mean) < 1e-3, (est, uniform_mean)


def test_dead_pad_slots_sample_with_zero_weight():
    """A shard whose tail segment is all episode pads (next_off == 0)
    keeps those slots out of training: any draw landing on one gets IS
    weight exactly 0 via the vmapped valid_mask gate."""
    dp, cap, seg = 2, 32, 8
    replay = _ring(cap=cap, seg=seg, alpha=1.0, beta=1.0, eps=0.0)
    mesh = make_mesh(dp=dp, tp=1)
    lcfg = LearnerConfig(batch_size=64)
    learner = DistLearner(dqn_family(lambda p, o: o, lcfg), replay, lcfg, mesh)
    rng = np.random.default_rng(1)
    states = []
    for d in range(dp):
        items, pris = _segs(replay, 2, rng,
                            next_off=3 if d == 0 else 0)
        states.append(replay.add(replay.init(), items, pris))
    state = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
    keys = jax.random.split(jax.random.key(5), dp)
    _, idx, w = learner._sample_weighted(state, keys, 32)
    w = np.asarray(w)
    valid = np.asarray(jax.vmap(replay.valid_mask)(state, idx))
    # shard 1 is ALL pads: every one of its weights must be zeroed
    assert (valid[1] == 0.0).all()
    np.testing.assert_array_equal(w[1], np.zeros_like(w[1]))
    assert (w[0] > 0.0).all()


# -- per-shard observability -----------------------------------------------


def test_shard_stats_reports_per_shard_fill_and_mass():
    """shard_stats: sizes/live/fill/tree_mass per shard, with frame-ring
    live counts excluding dead pads — the numbers the multichip lane
    and the run report publish."""
    dp, cap, seg = 2, 32, 8
    replay = _ring(cap=cap, seg=seg)
    mesh = make_mesh(dp=dp, tp=1)
    lcfg = LearnerConfig(batch_size=16)
    learner = DistLearner(dqn_family(lambda p, o: o, lcfg), replay, lcfg, mesh)
    state = learner.init({"w": jnp.zeros((2,), jnp.float32)}, None,
                         jax.random.key(0))
    rng = np.random.default_rng(2)
    items, pris = _segs(replay, 2, rng)
    # shard-varying liveness: shard 0 fully live, shard 1 half pads
    no = np.broadcast_to(np.asarray(items["next_off"]),
                         (dp, 2, seg)).copy()
    no[1, :, seg // 2:] = 0
    d_items = {k: jnp.broadcast_to(v, (dp,) + v.shape)
               for k, v in items.items()}
    d_items["next_off"] = jnp.asarray(no)
    state = learner.add(state, d_items,
                        jnp.broadcast_to(pris, (dp,) + pris.shape))
    stats = learner.shard_stats(state)
    assert stats["sizes"] == [16, 16]
    assert stats["live"] == [16, 8]
    assert stats["fill"] == [0.5, 0.5]
    assert stats["fill_min"] == stats["fill_max"] == 0.5
    assert len(stats["tree_mass"]) == dp
    assert all(m > 0 for m in stats["tree_mass"])


def test_live_transitions_single_and_sharded():
    """live_transitions reduces only the slot axis: scalar on a
    single-chip state, [dp] on the stacked lockstep state."""
    replay = _ring(cap=32, seg=8)
    rng = np.random.default_rng(4)
    items, pris = _segs(replay, 2, rng)
    s1 = replay.add(replay.init(), items, pris)
    assert int(replay.live_transitions(s1)) == 16
    sd = replay.add_lockstep(_stack1(replay.init()), _stack1(items),
                             pris[None])
    assert np.asarray(replay.live_transitions(sd)).tolist() == [16]


# -- the ring's rows are 32-bit words, on the dp mesh too (PR 29) ----------


@pytest.mark.parametrize("chunks", (1, 4))
@pytest.mark.parametrize("write", ("lockstep", "per_shard"))
def test_dp2_word_rows_sample_byte_for_byte(write, chunks):
    """dp=2, both dist writes (add_lockstep: one DUS over the shard
    axis; add_at_lockstep: per-shard unrolled DUS at each shard's own
    segment): every shard's vmapped sample_items returns its own
    segments' bytes, and its frames leaf is the single-chip ring's
    words for the same writes."""
    replay = _ring()
    dp, rng = 2, np.random.default_rng(29)
    stack2 = lambda *xs: jax.tree.map(lambda *v: jnp.stack(v), *xs)
    per_shard = [[_segs(replay, 3, rng) for _ in range(2)]
                 for _ in range(dp)]
    singles = [replay.init() for _ in range(dp)]
    state = stack2(*singles)
    for n in range(2):
        items = stack2(*[per_shard[d][n][0] for d in range(dp)])
        pris = jnp.stack([per_shard[d][n][1] for d in range(dp)])
        state = replay.add_lockstep(state, items, pris)
        singles = [replay.add(singles[d], *per_shard[d][n])
                   for d in range(dp)]
    if write == "per_shard":
        seg0 = jnp.asarray([1, 4], jnp.int32)
        extra = [_segs(replay, 2, rng) for _ in range(dp)]
        state = replay.add_at_lockstep(
            state, stack2(*[e[0] for e in extra]),
            jnp.stack([e[1] for e in extra]), seg0)
        singles = [replay.add_at(singles[d], *extra[d], seg0[d])
                   for d in range(dp)]
    assert state.storage["frames"].dtype == jnp.uint32
    assert state.storage["frames"].shape == (
        dp, replay.S * replay.F, replay.frame_row // 4)
    keys = jax.random.split(jax.random.key(3), dp)
    got, idx, probs = jax.vmap(
        lambda rs, k: replay.sample_items(rs, k, 16, chunks))(state, keys)
    for d in range(dp):
        for k in state.storage:
            np.testing.assert_array_equal(
                np.asarray(state.storage[k][d]),
                np.asarray(singles[d].storage[k]), err_msg=k)
        want, want_idx, want_probs = replay.sample_items(
            singles[d], keys[d], 16, chunks)
        np.testing.assert_array_equal(np.asarray(idx[d]),
                                      np.asarray(want_idx))
        np.testing.assert_array_equal(np.asarray(probs[d]),
                                      np.asarray(want_probs))
        # the single-chip sample is held to the segments themselves in
        # tests/test_frame_ring.py; here: to this shard's staged frames
        byte_rows = np.asarray(jax.lax.bitcast_convert_type(
            singles[d].storage["frames"], jnp.uint8)).reshape(
                -1, replay.frame_row)[:, :replay.frame_bytes]
        for j, t in enumerate(np.asarray(idx[d])):
            row = (t // replay.B) * replay.F + t % replay.B
            off = int(singles[d].storage["next_off"][t])
            for side, r0 in (("obs", row), ("next_obs", row + off)):
                np.testing.assert_array_equal(
                    np.asarray(got[side][d, j]),
                    np.moveaxis(byte_rows[r0:r0 + replay.stack].reshape(
                        replay.stack, replay.h, replay.w), 0, -1),
                    err_msg=side)
        for k in want:
            np.testing.assert_array_equal(np.asarray(got[k][d]),
                                          np.asarray(want[k]), err_msg=k)


def test_dp2_word_ring_is_written_in_place():
    """The lockstep add aliases the donated [dp, rows, words] ring."""
    replay = FrameRingReplay(capacity=4096, seg_transitions=16, n_step=3,
                             obs_shape=(84, 84, 4))
    shapes = jax.eval_shape(
        lambda: jax.tree.map(lambda x: jnp.stack([x, x]), replay.init()))
    ring_bytes = shapes.storage["frames"].size * 4
    items = {
        "seg_frames": jax.ShapeDtypeStruct((2, 4, replay.F, 84, 84),
                                           jnp.uint8),
        **{k: jax.ShapeDtypeStruct((2, 4, replay.B), dt) for k, dt in (
            ("action", jnp.int32), ("reward", jnp.float32),
            ("discount", jnp.float32), ("next_off", jnp.int32))}}
    pris = jax.ShapeDtypeStruct((2, 4, replay.B), jnp.float32)
    mem = jax.jit(replay.add_lockstep, donate_argnums=0).lower(
        shapes, items, pris).compile().memory_analysis()
    assert mem.temp_size_in_bytes < ring_bytes // 4, (
        mem.temp_size_in_bytes, ring_bytes)
    assert mem.alias_size_in_bytes >= ring_bytes
