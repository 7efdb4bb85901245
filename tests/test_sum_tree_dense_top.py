"""`ops/sum_tree.update` repairs the top of the tree in one dense pass
(ISSUE 36): by index through the levels wider than the batch, then the
contiguous prefix above them as pairwise sums. The tree it leaves is the
all-indexed level walk's BIT FOR BIT; `level_walk` below is that walk as
it stood before the change, kept here as the reference.

`ops/sum_tree.sample` reads the top of the tree densely too (ISSUE 51):
a select over a level's own left children down to level
`dense_descent_levels`, by index below. The draw is the all-indexed
descent's BIT FOR BIT; `indexed_descent` below is that descent.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.configs import get_config
from ape_x_dqn_tpu.ops import sum_tree
from ape_x_dqn_tpu.replay import prioritized
from ape_x_dqn_tpu.replay.frame_ring import FrameRingReplay
from ape_x_dqn_tpu.replay.prioritized import PrioritizedReplay, ring_finish

CAPACITIES = [8, 64, 4096, 2 ** 16]
BATCHES = [1, 2, 16, 512, 2048]
DP = 4


def level_walk(tree, leaf_idx, priorities):
    """The parent commit's `update`: a leaf scatter, then two gathers
    and a scatter of every index for each of log2(capacity) levels."""
    cap = sum_tree.capacity_of(tree)
    node = leaf_idx.astype(jnp.int32) + cap
    tree = tree.at[node].set(priorities.astype(jnp.float32))
    for _ in range(cap.bit_length() - 1):
        node = node >> 1
        tree = tree.at[node].set(tree[2 * node] + tree[2 * node + 1])
    return tree


def _bits(tree) -> np.ndarray:
    return np.asarray(tree).view(np.uint32)


def _batches(cap: int, n: int, rounds: int = 3):
    """`rounds` batches of n leaves: duplicates inside a batch (all of
    it when n > cap), zero priorities, leaves hit again by a later
    batch, and magnitudes 1e-3 .. 1e3 so that a sum's rounding shows."""
    rng = np.random.default_rng(cap * 31 + n)
    for _ in range(rounds):
        idx = rng.integers(0, cap, n)
        idx[: n // 4] = idx[n // 4: 2 * (n // 4)]
        pri = 10.0 ** rng.uniform(-3, 3, n)
        pri[rng.random(n) < 0.2] = 0.0
        # duplicates carry ONE value: which write a scatter keeps is
        # the backend's to choose, and not what this file is about
        _, first = np.unique(idx, return_index=True)
        pri = pri[first][np.searchsorted(idx[first], idx)]
        yield jnp.asarray(idx, jnp.int32), jnp.asarray(pri, jnp.float32)


def _both(cap: int, n: int):
    got, want = sum_tree.init(cap), sum_tree.init(cap)
    new, old = jax.jit(sum_tree.update), jax.jit(level_walk)
    for idx, pri in _batches(cap, n):
        got, want = new(got, idx, pri), old(want, idx, pri)
        yield got, want


@pytest.mark.parametrize("n", BATCHES)
@pytest.mark.parametrize("cap", CAPACITIES)
def test_the_tree_is_the_level_walks_bit_for_bit(cap, n):
    for got, want in _both(cap, n):
        np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("n", BATCHES)
@pytest.mark.parametrize("cap", CAPACITIES)
def test_every_node_is_the_sum_of_its_children(cap, n):
    for tree, _ in _both(cap, n):
        tree = np.asarray(tree)
        assert tree[0] == 0.0
        np.testing.assert_array_equal(
            tree[1:cap], tree[2:2 * cap:2] + tree[3:2 * cap:2])
    assert tree[1] > 0.0


@pytest.mark.parametrize("dense", [0, 1, 5, 7, 8, 11])
def test_every_split_of_one_tree_is_the_level_walk(dense, monkeypatch):
    # the constant makes every level of this file's trees dense; the
    # splits a wider tree gets are held here by hand, across the width
    # (LANES) where the pair sums change their view
    monkeypatch.setattr(sum_tree, "dense_levels", lambda capacity, n: dense)
    for got, want in _both(4096, 16):
        np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("cap", CAPACITIES + [2 ** 14, 2 ** 20])
def test_dense_levels_follows_the_shapes(cap):
    depth = cap.bit_length() - 1
    got = [sum_tree.dense_levels(cap, n) for n in range(0, 4200)]
    assert got[0] == 0
    assert all(0 <= d <= depth for d in got)
    assert got == sorted(got), "monotone in n"
    assert all(d == depth for d in got[cap:]), "n >= capacity"
    assert sum_tree.dense_levels(cap, 10 * cap) == depth
    # a level is dense only with every level above it, so the split is
    # one number, and a level l is dense exactly when its 2^l nodes are
    # no more than the constant allows the batch
    for n in (1, 2, 16, 512, 2048):
        dense = sum_tree.dense_levels(cap, n)
        assert all((1 << l) <= n * sum_tree.DENSE_NODES_PER_INDEX
                   for l in range(dense))
        assert dense == depth or \
            (1 << dense) > n * sum_tree.DENSE_NODES_PER_INDEX


@pytest.mark.parametrize("site, dense", [
    ((2 ** 20, 2048), 20),   # pong: K*B leaves a macro-step
    ((2 ** 20, 512), 20),    # atari57 dp=4: a shard's leaves
    ((2 ** 20, 16), 20),     # one ingested segment
    ((2 ** 14, 256), 14),    # r2d2
    ((2 ** 16, 16), 16),     # glm47_flash
    ((4096, 2), 12),         # trinity_mini
    ((2 ** 22, 2), 17),      # where an indexed level is left
])
def test_the_splits_of_the_deployments_call_sites(site, dense):
    # the facts PERF.md quotes
    assert sum_tree.dense_levels(*site) == dense


# -- the read side: the descent (ISSUE 51) -----------------------------------

def indexed_descent(tree, rng, batch, size=None, chunks=1):
    """The parent commit's `sample`: log2(capacity) gathers of every
    draw's left child, each waiting on the one before."""
    cap = sum_tree.capacity_of(tree)
    tot = tree[1]
    u = (jnp.arange(batch, dtype=jnp.float32)
         + jax.random.uniform(rng, (batch,))) / batch * tot
    u = sum_tree.chunk_major(u, chunks)
    idx = jnp.ones(batch, jnp.int32)
    for _ in range(cap.bit_length() - 1):
        left = tree[2 * idx]
        go_right = u >= left
        u = jnp.where(go_right, u - left, u)
        idx = 2 * idx + go_right.astype(jnp.int32)
    leaf = idx - cap
    if size is not None:
        leaf = jnp.minimum(leaf, jnp.maximum(size, 1) - 1)
    return leaf, tree[cap + leaf] / jnp.maximum(tot, 1e-12)


def _tree_of(kind: str, cap: int, n: int):
    """-> (tree, size): `size` None where the whole ring is live."""
    if kind == "all_zero":
        return sum_tree.init(cap), jnp.int32(0)
    if kind == "one_leaf":  # every draw has to find it, from either side
        return sum_tree.update(sum_tree.init(cap),
                               jnp.asarray([cap // 3], jnp.int32),
                               jnp.asarray([7.25], jnp.float32)), None
    if kind == "partly_filled":  # the clamp by `size` bites
        live = max(2, cap // 5)
        rng = np.random.default_rng(cap + n)
        tree = sum_tree.update(
            sum_tree.init(cap), jnp.arange(live, dtype=jnp.int32),
            jnp.asarray(10.0 ** rng.uniform(-3, 3, live), jnp.float32))
        return tree, jnp.int32(live - 1)
    tree = sum_tree.init(cap)  # "duplicates": `_batches`' updates
    for idx, pri in _batches(cap, max(n, cap // 8), rounds=4):
        tree = sum_tree.update(tree, idx, pri)
    return tree, None


# (capacity, draws, chunks): pong's macro-step, a shard's of atari57
# dp=4, r2d2's, one with an indexed level left, one with every level dense
DRAWS = [(2 ** 20, 2048, 4), (2 ** 20, 512, 4), (2 ** 14, 256, 4),
         (4096, 128, 1), (64, 128, 1)]
TREES = ["duplicates", "partly_filled", "all_zero", "one_leaf"]


def _assert_same_draw(got, want):
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(_bits(g), _bits(w))


@pytest.mark.parametrize("kind", TREES)
@pytest.mark.parametrize("cap, n, chunks", DRAWS)
def test_the_draw_is_the_indexed_descents_bit_for_bit(cap, n, chunks, kind):
    assert sum_tree.dense_descent_levels(cap, n) > 0
    tree, size = _tree_of(kind, cap, n)
    new = jax.jit(sum_tree.sample, static_argnums=(2, 4))
    old = jax.jit(indexed_descent, static_argnums=(2, 4))
    for seed in range(3):
        key = jax.random.key(seed)
        got, want = new(tree, key, n, size, chunks), \
            old(tree, key, n, size, chunks)
        _assert_same_draw(got, want)
    leaf = np.asarray(got[0])
    assert leaf.min() >= 0 and leaf.max() < cap
    if kind == "one_leaf":
        assert (leaf == cap // 3).all() and (np.asarray(got[1]) == 1).all()
    if kind == "all_zero":
        assert (leaf == 0).all()


@pytest.mark.parametrize("dense", range(13))
def test_every_split_of_one_descent_is_one_draw(dense, monkeypatch):
    # every level dense down to none: the splits a shape does not get by
    # the rule are held here by hand, across the width (LANES) where the
    # left children's pair sums change their view
    cap, n = 4096, 256
    monkeypatch.setattr(sum_tree, "dense_descent_levels", lambda c, m: dense)
    tree, size = _tree_of("partly_filled", cap, n)
    for t, s in ((tree, size), _tree_of("duplicates", cap, n)):
        key = jax.random.key(dense)
        _assert_same_draw(sum_tree.sample(t, key, n, s, 4),
                          indexed_descent(t, key, n, s, 4))


@pytest.mark.parametrize("cap", CAPACITIES + [2 ** 14, 2 ** 20])
def test_dense_descent_levels_follows_the_shapes(cap):
    depth = cap.bit_length() - 1
    got = [sum_tree.dense_descent_levels(cap, n) for n in range(0, 4200)]
    assert not any(got[:sum_tree.LANES]), "under a row of lanes: the walk"
    # from a row of lanes up one split, whatever the batch: a select and
    # a gather both cost in proportion to the draws
    assert set(got[sum_tree.LANES:]) == {got[-1]}
    assert 0 < got[-1] <= depth
    assert got[-1] == depth or \
        (1 << got[-1]) > sum_tree.DENSE_NODES_PER_DRAW >= (1 << (got[-1] - 1))


@pytest.mark.parametrize("site, dense", [
    ((2 ** 20, 2048), 14),   # pong, pong_live: K*B draws a macro-step
    ((2 ** 20, 512), 14),    # atari57 dp=4: a shard's draws
    ((2 ** 14, 256), 14),    # r2d2: every level
    ((2 ** 20, 256), 14),    # apex_dpg
    ((2 ** 16, 16), 0),      # glm47_flash
    ((4096, 2), 0),          # trinity_mini
    ((2 ** 11, 1), 0),       # smallthinker
    ((2 ** 13, 1), 0),       # ouro, kimi_linear
    ((2 ** 11, 2), 0),       # lfm2_moe
    ((4096, 2048), 12),      # tests/test_cycle_scopes.py's `pong`
    ((64, 128), 6),          # every level
])
def test_the_dense_levels_of_the_deployments_draws(site, dense):
    # the mechanism engages by shape at trace time, so this table is its
    # counter: the facts PERF.md quotes
    assert sum_tree.dense_descent_levels(*site) == dense


@pytest.mark.parametrize("preset", [
    "glm47_flash_q", "trinity_mini_q", "smallthinker_21b_q", "ouro_2p6b_q",
    "kimi_linear_48b_q", "lfm2_24b_q", "glm_tiny_q", "trinity_tiny_q",
    "smallthinker_tiny_q", "ouro_tiny_q", "kimi_linear_tiny_q",
    "lfm2_tiny_q"])
def test_a_decoder_presets_draw_keeps_the_indexed_walk(preset):
    cfg = get_config(preset)
    n = cfg.learner.batch_size * cfg.learner.sample_chunk
    assert n < sum_tree.LANES
    for cap in (64, cfg.replay.capacity, 2 ** 20):
        assert sum_tree.dense_descent_levels(cap, n) == 0


@pytest.mark.parametrize("dense", [None, 0, 5], ids=["shipped", "0", "5"])
@pytest.mark.parametrize("form", ["directed", "lockstep"])
def test_the_descent_under_both_vmaps(form, dense, monkeypatch):
    # the mesh draws as `vmap(shard_sample)(replay_state, keys)`:
    # "directed" is that; "lockstep" shares one key between the shards
    cap, n, chunks = 4096, 128, 4
    if dense is not None:
        monkeypatch.setattr(sum_tree, "dense_descent_levels",
                            lambda c, m: dense)
    trees, sizes = zip(*[
        _tree_of("partly_filled", cap, n + shard) for shard in range(DP)])
    trees, sizes = jnp.stack(trees), jnp.stack(sizes)
    keys = jax.random.split(jax.random.key(3), DP)
    rng_axis = 0 if form == "directed" else None
    rng = keys if form == "directed" else keys[0]

    def over_shards(fn):
        return jax.vmap(lambda t, k, s: fn(t, k, n, s, chunks),
                        in_axes=(0, rng_axis, 0))(trees, rng, sizes)

    got, want = over_shards(sum_tree.sample), over_shards(indexed_descent)
    assert got[0].shape == (DP, n)
    _assert_same_draw(got, want)
    for shard in range(DP):  # and each shard's is its own tree's
        alone = indexed_descent(
            trees[shard], keys[shard] if form == "directed" else keys[0],
            n, sizes[shard], chunks)
        _assert_same_draw([g[shard] for g in got], alone)


# -- through the replays --------------------------------------------------


@pytest.mark.parametrize("dense", [None, 5], ids=["shipped", "5-levels"])
@pytest.mark.parametrize("form", ["plain", "lockstep", "directed"])
def test_ring_finish_under_both_vmaps(form, dense, monkeypatch):
    cap, n = 4096, 64
    if dense is not None:  # a split that leaves indexed levels
        monkeypatch.setattr(sum_tree, "dense_levels", lambda c, m: dense)
    rng = np.random.default_rng(5)
    lead = () if form == "plain" else (DP,)
    shape = lead + (n,)
    idx = rng.integers(0, cap, shape if form == "directed" else (n,))
    pri = rng.lognormal(0, 2, shape)
    args = (jnp.asarray(idx, jnp.int32), jnp.asarray(pri, jnp.float32),
            jnp.zeros(lead, jnp.int32), jnp.zeros(lead, jnp.int32), lead)
    tree0 = jnp.zeros(lead + (2 * cap,), jnp.float32)
    got = ring_finish(tree0, *args)[0]
    monkeypatch.setattr(sum_tree, "update", level_walk)
    want = ring_finish(tree0, *args)[0]
    assert got.shape == want.shape == lead + (2 * cap,)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    assert float(jnp.min(got[..., 1])) > 0.0


def _states(replay, step, monkeypatch):
    """-> the replay's state after `step`, under today's `update` and
    under the level walk."""
    got = step(replay.init(ITEM))
    monkeypatch.setattr(sum_tree, "update", level_walk)
    return got, step(replay.init(ITEM))


ITEM = {"x": jax.ShapeDtypeStruct((3,), jnp.float32)}


def test_through_update_priorities(monkeypatch):
    replay = PrioritizedReplay(capacity=1024)
    rng = np.random.default_rng(11)
    items = {"x": jnp.asarray(rng.random((512, 3)), jnp.float32)}
    td0 = jnp.asarray(rng.random(512), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 512, 96), jnp.int32)
    td1 = jnp.asarray(rng.lognormal(0, 2, 96), jnp.float32)

    def step(state):
        state = replay.add(state, items, td0)
        return replay.update_priorities(state, idx, td1)

    got, want = _states(replay, step, monkeypatch)
    np.testing.assert_array_equal(_bits(got.tree), _bits(want.tree))
    assert float(sum_tree.total(got.tree)) > 0.0
    assert prioritized.sum_tree is sum_tree  # the patch reached the replay


def test_through_a_frame_ring_add(monkeypatch):
    seg, n_step, stack, hw = 8, 3, 4, 6
    replay = FrameRingReplay(capacity=256, seg_transitions=seg,
                             n_step=n_step, obs_shape=(hw, hw, stack))
    rng = np.random.default_rng(13)
    g, frames = 4, seg + n_step + stack - 1
    items = {
        "seg_frames": jnp.asarray(
            rng.integers(0, 255, (g, frames, hw, hw)), jnp.uint8),
        "action": jnp.zeros((g, seg), jnp.int32),
        "reward": jnp.zeros((g, seg), jnp.float32),
        "discount": jnp.ones((g, seg), jnp.float32),
        # the last two slots of every segment are dead pads
        "next_off": jnp.asarray(
            np.tile([n_step] * (seg - 2) + [0, 0], (g, 1)), jnp.int32),
    }
    td = jnp.asarray(rng.lognormal(0, 2, (g, seg)), jnp.float32)

    def step(state):
        for _ in range(3):
            state = replay.add(state, items, td)
        return state

    got, want = _states(replay, step, monkeypatch)
    np.testing.assert_array_equal(_bits(got.tree), _bits(want.tree))
    leaves = np.asarray(sum_tree.leaves(got.tree))[: 3 * g * seg]
    assert (leaves.reshape(-1, seg)[:, -2:] == 0).all()
    assert (leaves.reshape(-1, seg)[:, :-2] > 0).all()


# -- what the chip's compiler makes of it ----------------------------------
# (on-chip-measurement guide, section 2: describe the chip inside a
# fixture, in this one file; nothing runs)

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1), num_slices=1)
    except Exception as e:
        pytest.skip(f"no v5e topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(jitted, *args):
    """`jitted` compiled for the device its arguments' shardings
    describe, the persistent cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache

    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jitted.lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()


def _compiled(jitted, *args):
    """-> (gather/scatter fusions, ops the entry computation runs one
    after another, HLO temp bytes) of `_compile(jitted, *args)`."""
    compiled = _compile(jitted, *args)
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    free = r"= \S+ (parameter|constant|bitcast|get-tuple-element|tuple)\("
    ops = [line for line in entry.splitlines()
           if " = " in line and not re.search(free, line)]
    indexed = [line for line in ops if "kind=kCustom" in line]
    temp = compiled.memory_analysis().temp_size_in_bytes
    return len(indexed), len(ops), temp


def _compiled_for_v5e(fn, cap: int, n: int, chip):
    """`_compiled` of an update `fn` for a described v5e."""
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    return _compiled(
        jax.jit(lambda t, i, p: fn(t, i, p), donate_argnums=(0,)),
        arg((2 * cap,), jnp.float32), arg((n,), jnp.int32),
        arg((n,), jnp.float32))


def _draw_compiled_for_v5e(fn, cap: int, n: int, chip):
    """`_compiled` of a descent `fn`, n draws in 4 chunks."""
    key = jax.eval_shape(lambda: jax.random.key(0))
    return _compiled(
        jax.jit(lambda t, k: fn(t, k, n, jnp.int32(cap), 4)),
        jax.ShapeDtypeStruct((2 * cap,), jnp.float32, sharding=chip),
        jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=chip))


def test_compiled_for_a_v5e_the_macro_step_walks_few_levels(one_chip):
    indexed, _, temp = _compiled_for_v5e(sum_tree.update, 2 ** 20, 2048,
                                         one_chip)
    before, _, _ = _compiled_for_v5e(level_walk, 2 ** 20, 2048, one_chip)
    assert before == 61       # the leaf scatter + 20 x (2 gathers, 1 scatter)
    assert indexed == 1       # the leaf scatter
    assert temp < 2 ** 20     # the rounds' intermediates live in VMEM


def test_compiled_for_a_v5e_a_batch_of_two_costs_no_more_ops(one_chip):
    _, ops, _ = _compiled_for_v5e(sum_tree.update, 4096, 2, one_chip)
    _, before, _ = _compiled_for_v5e(level_walk, 4096, 2, one_chip)
    assert ops <= before


def test_compiled_for_a_v5e_the_macro_steps_draw_gathers_seven_times(one_chip):
    indexed, _, temp = _draw_compiled_for_v5e(sum_tree.sample, 2 ** 20,
                                              2048, one_chip)
    before, _, _ = _draw_compiled_for_v5e(indexed_descent, 2 ** 20, 2048,
                                          one_chip)
    assert before == 21       # 20 levels + the leaves' priorities
    assert indexed == 7       # levels 14 .. 19 + the leaves' priorities
    # a select is a fused compare-select-reduce: no [8192, 2048] float32
    # (64 MiB) is ever an array
    assert temp < 2 ** 20


def test_compiled_for_a_v5e_a_draw_of_sixteen_is_the_indexed_descents(
        one_chip):
    now = _draw_compiled_for_v5e(sum_tree.sample, 2 ** 16, 16, one_chip)
    before = _draw_compiled_for_v5e(indexed_descent, 2 ** 16, 16, one_chip)
    assert now == before and now[0] == 17


# -- the slot server's decode step (ISSUE 56), in this file because it is
# the one that describes a chip -----------------------------------------------

def _compiled_decode_step(cfg, rows: int, chip):
    """-> (the server's slot program of `cfg`'s net at a decode step of
    `rows` rows, compiled for `chip` with the state donated as the
    server donates it and the matrices bfloat16; the net; its slot
    state as described shapes)."""
    from ape_x_dqn_tpu.models import build_network
    from ape_x_dqn_tpu.runtime import family

    net = build_network(cfg.network, None)

    def described(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype, sharding=chip)

    params = jax.tree.map(
        lambda x: described(x, jnp.bfloat16),
        jax.eval_shape(net.init, jax.random.PRNGKey(0)))
    slots, max_len, pool_tokens = family.slot_geometry(cfg, net.slot_block)
    state = jax.tree.map(described, jax.eval_shape(
        lambda: net.slot_state(slots, pool_tokens, max_len)))
    row = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=chip)
    compiled = _compile(
        jax.jit(family.server_apply_fn("decoder_q", net, cfg),
                donate_argnums=(1,)),
        params, state, {"obs": row, "slot": row, "base": row, "fresh": row})
    return compiled, net, state


def test_compiled_for_a_v5e_a_decode_step_moves_no_lightning_pool(
        one_chip, monkeypatch):
    """MiniCPM-SALA's served stage as `minicpm_sala_decode` runs it (the
    overrides of benchmarks/configs/minicpm_sala_9b_pp4_1chip.json:
    published layers 9-16, 48 slots), a decode step of 16 rows with the
    state donated as the server donates it: each lightning layer is one
    `lightning_step_slots` kernel on its pool in place - no copy of a
    [49, 32, 128, 128] pool (one is 0.25 ms a layer on the chip), the
    whole state aliased, and the [rows, 32, 128, 128] float32 gather,
    `after` and scatter operand (128 MiB a layer at 16 rows) gone from
    the temp."""
    import json
    import os

    from ape_x_dqn_tpu.ops import lightning_attention as la
    from ape_x_dqn_tpu.runtime.train import apply_overrides

    monkeypatch.setattr(la, "_interpret", lambda: False)
    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmarks", "configs",
            "minicpm_sala_9b_pp4_1chip.json")) as fh:
        served = json.load(fh)
    cfg = apply_overrides(get_config(served["preset"]), served["overrides"])
    compiled, net, state = _compiled_decode_step(cfg, 16, one_chip)
    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if "custom-call(" in line and "tpu_custom_call" in line]
    assert len(kernels) == 6
    assert all("sala.lightning.state" in line for line in kernels)
    assert not re.search(r"= f32\[49,32,128,128\]\S* copy\(", text)
    memory = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
    assert memory.alias_size_in_bytes >= held       # `len` is padded
    assert memory.temp_size_in_bytes < 2 ** 27      # 75.5 MiB at PR 56


# -- and the second net's (ISSUE 58) ------------------------------------------

@pytest.mark.parametrize("preset, rows", [("jamba2_3b_q", 128),
                                          ("jamba2_tiny_q", 2)])
def test_compiled_for_a_v5e_a_decode_step_moves_no_ssm_pool(
        one_chip, monkeypatch, preset, rows):
    """AI21-Jamba2-3B served whole as `jamba2_decode_wide` runs it (256
    slots, a decode step of 128 rows with the state donated as the
    server donates it), and the tiny preset: each Mamba layer is one
    `selective_scan_step_slots` kernel on its pool in place under
    `jamba.mamba.scan` - the chip's compiler takes the kernel at a
    [16, 5120] block, no copy of a [slots + 1, d_state, channels] pool,
    the whole state aliased, and the [rows, 16, 5120] float32 gather,
    `after` and scatter operand (40 MiB each a layer at 128 rows) gone
    from the temp. Since ISSUE 60 each attention layer of the published
    widths is one `attend_range` kernel under `jamba.attn.attend` that
    reads the key and value pools where they lie: no [rows, 1, 80, 128,
    128] gather of either (320 MiB each a layer at 128 rows) is left.
    The tiny preset's heads are 16 wide, not whole lanes, which the
    chip's compiler refuses: its walk stays the interpreter's."""
    from ape_x_dqn_tpu.ops import block_select_attention as bsa
    from ape_x_dqn_tpu.ops import selective_scan

    published = preset == "jamba2_3b_q"
    monkeypatch.setattr(selective_scan, "_interpret", lambda: False)
    if published:
        monkeypatch.setattr(bsa, "_interpret", lambda: False)
    compiled, net, state = _compiled_decode_step(get_config(preset), rows,
                                                 one_chip)
    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if "custom-call(" in line and "tpu_custom_call" in line]
    scans = [line for line in kernels
             if "jamba.mamba/jamba.mamba.scan" in line]
    walks = [line for line in kernels
             if "jamba.attn/jamba.attn.attend" in line]
    assert len(scans) == net.num_mamba
    assert len(walks) == (net.num_attention if published else 0)
    assert len(kernels) == len(scans) + len(walks)
    pool = ",".join(str(n) for n in state["ssm"][0].shape)
    assert not re.search(rf"= f32\[{pool}\]\S* copy\(", text)
    memory = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
    assert memory.alias_size_in_bytes >= held       # `len` is padded
    if published:
        assert net.num_mamba == 26 and pool == "257,16,5120"
        assert net.num_attention == 2 and "bf16[128,1,80,128,128]" not in text
        keys = ",".join(str(n) for n in state["k"][0].shape)
        assert keys == "1,2107392,128"
        assert not re.search(rf"= bf16\[{keys}\]\S* copy\(", text)
        # 0.177 GiB (0.611 at PR 58 with the attention layers' gathers,
        # 1.382 before it with the state's gather, `step` and scatter)
        assert memory.temp_size_in_bytes < 0.25 * 2 ** 30
