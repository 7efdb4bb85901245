"""Order of the K-batch draw (ISSUE 25): the replay emits the K*B draw
chunk-major (ops/sum_tree.py::chunk_major), so `_sample_stage`'s
[K, B, ...] view is a reshape and the sampled payload is never permuted.

Pinned here, for every storage layout x K x learner stack:
- the batch each SGD step sees is bit for bit what the old order of
  operations gave (draw in stratum order, gather, THEN
  `reshape(b, k, ...).swapaxes(0, 1)` over the gathered items);
- the dist write-back pairs (leaf, |TD|) exactly as the old inverse
  chunk transform did;
- in the traced program no transpose touches the sampled frames beyond
  the one per side `FrameRingReplay._gather` itself needs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.configs import LearnerConfig, NetworkConfig, ReplayConfig
from ape_x_dqn_tpu.envs.base import EnvSpec
from ape_x_dqn_tpu.models import build_network
from ape_x_dqn_tpu.ops import sum_tree
from ape_x_dqn_tpu.parallel.dist_learner import DistLearner
from ape_x_dqn_tpu.parallel.mesh import make_mesh
from ape_x_dqn_tpu.replay.frame_ring import FrameRingReplay
from ape_x_dqn_tpu.replay.prioritized import (PrioritizedReplay,
                                              UniformReplayDevice)
from ape_x_dqn_tpu.runtime.family import dqn_family, r2d2_family
from ape_x_dqn_tpu.runtime.learner import (
    SingleChipLearner, transition_item_spec)

STORAGES = ("flat", "seq", "ring")
KS = (1, 2, 4)
B = 8            # per-step batch (single chip) / per-shard batch (dist)
DP = 2
OBS = (6, 6, 4)  # frame-ring [H, W, stack]
RCFG = ReplayConfig(kind="sequence", seq_length=4, burn_in=1)


def _eq(a, b):
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), a, b)


def _filled(storage, seed, lead=()):
    """-> (replay, ReplayState filled with seeded items and spread-out
    priorities); `lead`=(dp,) stacks dp lockstep shards."""
    rng = np.random.default_rng(seed)
    if storage == "ring":
        replay = FrameRingReplay(capacity=128, seg_transitions=8,
                                 n_step=3, obs_shape=OBS)
        g, b, f = 12, replay.B, replay.F
        items = {
            "seg_frames": rng.integers(0, 255, (*lead, g, f, *OBS[:2]),
                                       dtype=np.uint8),
            "action": rng.integers(0, 4, (*lead, g, b)).astype(np.int32),
            "reward": rng.normal(size=(*lead, g, b)).astype(np.float32),
            "discount": np.full((*lead, g, b), 0.97, np.float32),
            "next_off": rng.integers(1, 4, (*lead, g, b)).astype(np.int32),
        }
        pri = rng.uniform(0.05, 3.0, (*lead, g, b)).astype(np.float32)
        spec = None
    else:
        n = 100
        if storage == "flat":
            items = {
                "obs": rng.normal(size=(*lead, n, 4)).astype(np.float32),
                "action": rng.integers(0, 2, (*lead, n)).astype(np.int32),
                "reward": rng.normal(size=(*lead, n)).astype(np.float32),
                "next_obs": rng.normal(size=(*lead, n, 4)
                                       ).astype(np.float32),
                "discount": np.full((*lead, n), 0.97, np.float32),
            }
        else:  # the sequence items R2D2 keeps in the flat replay
            items = {
                "obs": rng.normal(size=(*lead, n, 4, 2)).astype(np.float32),
                "actions": rng.integers(0, 2, (*lead, n, 4)
                                        ).astype(np.int32),
                "rewards": rng.normal(size=(*lead, n, 4)
                                      ).astype(np.float32),
                "terminals": np.zeros((*lead, n, 4), np.float32),
                "mask": np.ones((*lead, n, 4), np.float32),
                "init_c": rng.normal(size=(*lead, n, 8)).astype(np.float32),
                "init_h": rng.normal(size=(*lead, n, 8)).astype(np.float32),
            }
        pri = rng.uniform(0.05, 3.0, (*lead, n)).astype(np.float32)
        nl = len(lead)
        spec = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[nl + 1:], x.dtype),
            items)
        replay = PrioritizedReplay(capacity=128, item_spec=spec)
    if lead:
        rs = jax.vmap(lambda _: replay.init(spec))(jnp.arange(lead[0]))
        return replay, replay.add_lockstep(rs, items, jnp.asarray(pri))
    return replay, replay.add(replay.init(spec), items, jnp.asarray(pri))


def _single_learner(storage, replay):
    lcfg = LearnerConfig(batch_size=B)
    if storage == "seq":   # inherits _sample_stage from SingleChipLearner
        return SingleChipLearner(
            r2d2_family(None, lcfg, RCFG), replay, lcfg)
    return SingleChipLearner(
        dqn_family(None, lcfg), replay, lcfg)


def _dist_learner(storage, replay):
    lcfg = LearnerConfig(batch_size=B * DP)
    mesh = make_mesh(dp=DP, tp=1)
    if storage == "seq":
        return DistLearner(
            r2d2_family(None, lcfg, RCFG),
            replay, lcfg, mesh)
    return DistLearner(
        dqn_family(None, lcfg), replay, lcfg, mesh)


# -- the permutation itself ------------------------------------------------

@pytest.mark.parametrize("k", KS)
def test_chunk_major_is_the_old_chunk_transform(k):
    """Position j*B + i of the chunk-major order holds stratum i*K + j,
    and sum_tree.sample(chunks=K) returns exactly the stratum-order
    draw's leaves and probs in that order."""
    b = 16
    s = np.arange(k * b)
    got = np.asarray(sum_tree.chunk_major(jnp.asarray(s), k))
    for j in range(k):
        np.testing.assert_array_equal(got[j * b:(j + 1) * b],
                                      np.arange(b) * k + j)
    tree = sum_tree.update(
        sum_tree.init(256), jnp.arange(200, dtype=jnp.int32),
        jnp.asarray(np.random.default_rng(0).uniform(0.1, 2.0, 200),
                    jnp.float32))
    key = jax.random.key(5)
    leaf0, p0 = sum_tree.sample(tree, key, k * b, size=jnp.int32(200))
    leaf1, p1 = sum_tree.sample(tree, key, k * b, size=jnp.int32(200),
                                chunks=k)
    old = lambda x: np.asarray(x).reshape(b, k).swapaxes(0, 1).reshape(-1)
    np.testing.assert_array_equal(np.asarray(leaf1), old(leaf0))
    np.testing.assert_array_equal(np.asarray(p1), old(p0))


@pytest.mark.parametrize("k", KS)
def test_uniform_replay_permutes_indices_not_items(k):
    """UniformReplayDevice has no strata: it keeps its draw and applies
    the same permutation to the indices before the gather."""
    spec = transition_item_spec((4,), np.float32)
    replay = UniformReplayDevice(capacity=64, item_spec=spec)
    rng = np.random.default_rng(3)
    items = {
        "obs": rng.normal(size=(50, 4)).astype(np.float32),
        "action": rng.integers(0, 2, 50).astype(np.int32),
        "reward": rng.normal(size=50).astype(np.float32),
        "next_obs": rng.normal(size=(50, 4)).astype(np.float32),
        "discount": np.full(50, 0.97, np.float32),
    }
    rs = replay.add(replay.init(), items)
    key = jax.random.key(2)
    it0, idx0, _ = replay.sample_state(rs, key, k * B)
    it1, idx1, w1 = replay.sample_state(rs, key, k * B, chunks=k)
    old = lambda x: x.reshape(B, k, *x.shape[1:]).swapaxes(0, 1) \
        .reshape(x.shape)
    _eq((it1, idx1), jax.tree.map(old, (it0, idx0)))
    assert w1.shape == (k * B,)


@pytest.mark.parametrize("k", KS)
def test_frame_ring_gather_bytes_do_not_depend_on_its_order(k):
    """_gather (stack axis first in the index, one gather per chunk)
    returns, byte for byte, the batch-first gather + moveaxis it
    replaced, for every `chunks`."""
    replay, rs = _filled("ring", seed=19)
    idx = jnp.asarray(np.random.default_rng(1).integers(0, 96, k * B),
                      jnp.int32)
    st = rs.storage
    base = (idx // replay.B) * replay.F + idx % replay.B

    # the ring's rows as the bytes they hold (a row is 32-bit words)
    byte_rows = jax.lax.bitcast_convert_type(
        st["frames"], jnp.uint8).reshape(-1, replay.frame_row)

    def batch_first(rows_base):
        f = byte_rows[rows_base[:, None] + jnp.arange(replay.stack)]
        f = f[..., :replay.frame_bytes].reshape(
            -1, replay.stack, replay.h, replay.w)
        return jnp.moveaxis(f, 1, -1)

    got = replay._gather(rs, idx, k)
    assert got["obs"].dtype == jnp.uint8
    assert got["obs"].shape == (k * B, *OBS)
    _eq((got["obs"], got["next_obs"]),
        (batch_first(base), batch_first(base + st["next_off"][idx])))


# -- single chip -------------------------------------------------------------

def _old_sample_stage(replay, rs, key, k, b):
    """The parent's _sample_stage: stratum-order draw, gather, then the
    chunk transform over everything gathered."""
    items, idx, is_w = replay.sample_state(rs, key, k * b)
    pri = replay.leaf_priorities(rs, idx)

    def chunked(x):
        return x.reshape(b, k, *x.shape[1:]).swapaxes(0, 1)

    is_w_k = chunked(is_w)
    is_w_k = is_w_k / jnp.maximum(is_w_k.max(axis=1, keepdims=True), 1e-12)
    return jax.tree.map(chunked, items), chunked(idx), is_w_k, chunked(pri)


@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("storage", STORAGES)
def test_sample_stage_equals_old_order_single_chip(storage, k):
    replay, rs = _filled(storage, seed=11)
    learner = _single_learner(storage, replay)
    key = jax.random.key(7)
    new = jax.jit(learner._sample_stage, static_argnums=2)(rs, key, k)
    old = jax.jit(_old_sample_stage, static_argnums=(0, 3, 4))(
        replay, rs, key, k, B)
    assert new[1].shape == (k, B)
    _eq(new, old)


# -- dist stack, dp=2 on host devices ---------------------------------------

def _old_dist_sample_stage(learner, rs, sk, k):
    """The parent's dist _sample_stage: per-shard stratum-order draw,
    gather, then reshape(dp, b, k).moveaxis(2, 0) over the items."""
    items, idx, w = learner._sample_weighted(rs, sk, k * learner.b_local)
    pri = jax.vmap(learner.replay.leaf_priorities)(rs, idx)

    def chunked(x):
        y = x.reshape(x.shape[0], learner.b_local, k, *x.shape[2:])
        return jnp.moveaxis(y, 2, 0)

    return jax.tree.map(chunked, items), idx, chunked(w), pri


def _to_chunk_major(x, k):
    """[dp, b*k] stratum order -> [dp, k*b] chunk-major."""
    return x.reshape(x.shape[0], -1, k).swapaxes(1, 2).reshape(x.shape)


@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("storage", STORAGES)
def test_sample_stage_equals_old_order_dist(storage, k):
    replay, rs = _filled(storage, seed=13, lead=(DP,))
    learner = _dist_learner(storage, replay)
    sk = jax.random.split(jax.random.key(9), DP)
    items_k, idx, w_k, pri = jax.jit(
        learner._sample_stage, static_argnums=2)(rs, sk, k)
    o_items, o_idx, o_w, o_pri = jax.jit(
        _old_dist_sample_stage, static_argnums=(0, 3))(learner, rs, sk, k)
    assert w_k.shape == (k, DP, B) and idx.shape == (DP, k * B)
    _eq((items_k, w_k), (o_items, o_w))
    # idx and pri stay un-chunked [dp, K*b_local], now in the draw's
    # own (chunk-major) order
    _eq((idx, pri), (_to_chunk_major(o_idx, k), _to_chunk_major(o_pri, k)))


@pytest.mark.parametrize("k", KS)
def test_dist_learn_k_writes_what_the_old_inverse_transform_wrote(k):
    """learn_k's write-back (plain concatenate of the td parts against
    the chunk-major idx) leaves the same shard trees as the old
    `td_all[d, i*k + j] = parts[j][d, i]` against stratum-order idx, on
    a draw without duplicate leaves."""
    spec_env = EnvSpec(obs_shape=(4,), obs_dtype=np.dtype(np.float32),
                       discrete=True, num_actions=2)
    net = build_network(
        NetworkConfig(kind="mlp", mlp_hidden=(32,), dueling=False,
                      compute_dtype="float32"), spec_env)
    params = net.init(jax.random.key(0), jnp.zeros((1, 4)))
    lcfg = LearnerConfig(batch_size=B * DP, target_sync_every=3)
    learner = DistLearner(
        dqn_family(net.apply, lcfg), PrioritizedReplay(capacity=256), lcfg,
        make_mesh(dp=DP, tp=1))
    state = learner.init(params, transition_item_spec((4,), jnp.float32),
                         jax.random.key(1))
    rng = np.random.default_rng(4)
    n = 256   # full ring, equal priorities: every stratum has own leaves
    items = {
        "obs": rng.normal(size=(DP, n, 4)).astype(np.float32),
        "action": rng.integers(0, 2, (DP, n)).astype(np.int32),
        "reward": rng.normal(size=(DP, n)).astype(np.float32),
        "next_obs": rng.normal(size=(DP, n, 4)).astype(np.float32),
        "discount": np.full((DP, n), 0.97, np.float32),
    }
    state = learner.add(state, items, jnp.ones((DP, n)))
    sample, rng2 = learner.sample_k(state, k)
    items_k, idx, w_k, _ = sample
    idx_np = np.asarray(idx)
    assert all(len(np.unique(r)) == r.size for r in idx_np)

    @jax.jit
    def old_write_back(state):
        p, t, o, s = (state.params, state.target_params, state.opt_state,
                      state.step)
        parts = []
        for j in range(k):
            it = jax.tree.map(lambda x: x[j], items_k)
            p, t, o, s, td, _ = learner._sgd_step(p, t, o, s, it, w_k[j])
            parts.append(td)
        td_all = jnp.moveaxis(jnp.stack(parts, axis=0), 0, 2) \
            .reshape(DP, k * B)
        idx_old = idx.reshape(DP, k, B).swapaxes(1, 2).reshape(DP, k * B)
        write = jax.vmap(learner.replay.update_priorities)
        return (write(state.replay, idx_old, td_all).tree,
                write(state.replay, idx, jnp.concatenate(parts, 1)).tree)

    before = np.asarray(state.replay.tree)[:, n:]
    want, paired_new = map(np.asarray, old_write_back(state))
    # the same |TD|s paired the new way: bit for bit the same trees
    np.testing.assert_array_equal(paired_new, want)
    state, _ = learner.learn_k(state._replace(rng=rng2), sample, k)
    got = np.asarray(state.replay.tree)
    # learn_k is another XLA program than this test's replica of its
    # loop, so its |TD|s may differ in the last bit: same leaves
    # written, same priorities to float tolerance
    np.testing.assert_array_equal(got[:, n:] != before,
                                  want[:, n:] != before)
    assert (got[:, n:] != before).sum() == DP * k * B
    np.testing.assert_allclose(got, want, rtol=1e-5)


# -- no second pass over the sampled frames ---------------------------------

def _image_transposes(jaxpr, min_bytes, shard_axis):
    """Bytes of sampled-frame operands (uint8 pixels or the uint32
    words the ring keeps them in, >= min_bytes each) that `transpose`
    equations reorder, nested jaxprs included. With `shard_axis` (the
    dist stack's leading dp axis, extent 1 on each chip) a transpose
    that only moves that axis past the others is free and not
    counted."""
    total = 0
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    total += _image_transposes(inner, min_bytes, shard_axis)
        if eqn.primitive.name != "transpose":
            continue
        aval = eqn.invars[0].aval
        nbytes = aval.size * aval.dtype.itemsize
        if aval.dtype not in (jnp.uint8, jnp.uint32) or nbytes < min_bytes:
            continue
        perm = [p for p in eqn.params["permutation"] if p != shard_axis]
        if perm != sorted(perm):
            total += nbytes
    return total


@pytest.mark.parametrize("stack", ("single", "dist"))
def test_sampled_frames_are_transposed_once(stack):
    """k=4, frame ring: the transposes over the sampled frames in
    _sample_stage are those of FrameRingReplay._gather alone — per
    side (obs, next_obs) one pass over the gathered words (rows x
    words, per pixel phase) and the [H,W,B,stack] -> [B,H,W,stack]
    relabelling of the result, which is conv1's layout and no copy on
    the chip. The parent's chunked() adds a pass per side; the helper
    counts it (checked on a copy of it below)."""
    k = 4
    h, w, st = OBS
    if stack == "single":
        replay, rs = _filled("ring", seed=17)
        learner = _single_learner("ring", replay)
        key, shard_axis = jax.random.key(1), None
        gather = lambda rs, idx: replay._gather(rs, idx, k)
        idx = jnp.zeros((k * B,), jnp.int32)
        old = lambda rs, key: _old_sample_stage(replay, rs, key, k, B)
        chips = 1
    else:
        replay, rs = _filled("ring", seed=17, lead=(DP,))
        learner = _dist_learner("ring", replay)
        key, shard_axis = jax.random.split(jax.random.key(1), DP), 0
        gather = jax.vmap(lambda rs, idx: replay._gather(rs, idx, k))
        idx = jnp.zeros((DP, k * B), jnp.int32)
        old = lambda rs, key: _old_dist_sample_stage(learner, rs, key, k)
        chips = DP
    images = chips * k * B * h * w * st           # bytes of one side
    words = chips * k * B * st * replay.frame_row  # its gathered rows
    count = lambda fn, *a: _image_transposes(
        jax.make_jaxpr(fn)(*a).jaxpr, B * h * w, shard_axis)
    in_gather = count(gather, rs, idx)
    assert in_gather == 2 * (words + images)
    assert count(lambda rs, key: learner._sample_stage(rs, key, k),
                 rs, key) == in_gather
    assert count(old, rs, key) == in_gather + 2 * images  # chunked()'s
