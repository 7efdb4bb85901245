"""Tile-exact pixel packing + in-place ring-write semantics
(replay/packing.py) and the HBM budget check (utils/hbm.py).

These encode the round-5 HBM findings: on TPU a [cap, H, W] u8 buffer
pads 1.6x under the (32, 128) tile and XLA inserts a full-buffer
relayout copy in every gather/scatter program over it (measured 25.1GB
for the pong preset's 9.47GB ring — OOM), while packed byte rows +
dynamic_update_slice ring writes compile to temp=0 in-place graphs.
CPU tests can't see layouts, so they pin the SEMANTICS (roundtrips,
skip-to-head wrap, budget math); the compiled-memory numbers live in
PERF.md "HBM budget".
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.configs import get_config
from ape_x_dqn_tpu.replay.packing import (GATHER_ROW_MAX_BYTES,
                                          PixelPacker, pad128, packable,
                                          ring_write_start, row_layout)
from ape_x_dqn_tpu.replay.prioritized import (PrioritizedReplay,
                                              UniformReplayDevice)
from ape_x_dqn_tpu.utils import hbm


# ---------------------------------------------------------------------------
# PixelPacker


def test_pad128():
    assert pad128(7056) == 7168
    assert pad128(128) == 128
    assert pad128(1) == 128


def test_packable_selects_large_u8_leaves_only():
    sds = jax.ShapeDtypeStruct
    assert packable(sds((84, 84, 4), jnp.uint8))
    assert packable(sds((22, 84, 84), jnp.uint8))
    assert not packable(sds((4,), jnp.float32))       # small f32 vector
    assert not packable(sds((84, 84), jnp.float32))   # not u8
    assert not packable(sds((8, 8), jnp.uint8))       # too small to matter


def test_packer_roundtrip_preserves_pixels():
    spec = {
        "obs": jax.ShapeDtypeStruct((84, 84, 4), jnp.uint8),
        "action": jax.ShapeDtypeStruct((), jnp.int32),
    }
    packer = PixelPacker(spec)
    assert packer.packs_anything
    stored = packer.storage_spec(spec)
    assert stored["obs"].shape == (pad128(84 * 84 * 4),)
    assert stored["obs"].dtype == jnp.uint8
    assert stored["action"].shape == ()  # untouched

    rng = np.random.default_rng(0)
    items = {
        "obs": jnp.asarray(rng.integers(0, 255, (5, 84, 84, 4)), jnp.uint8),
        "action": jnp.asarray(rng.integers(0, 4, 5), jnp.int32),
    }
    rows = packer.encode(items)
    assert rows["obs"].shape == (5, pad128(84 * 84 * 4))
    back = packer.decode(rows)
    np.testing.assert_array_equal(np.asarray(back["obs"]),
                                  np.asarray(items["obs"]))
    np.testing.assert_array_equal(np.asarray(back["action"]),
                                  np.asarray(items["action"]))


# an item wider than a TPU gather fetches whole is stored one row per
# leading-axis slice (ISSUE 26: an R2D2 sequence as ONE 585,728 B row
# made XLA copy the whole replay per sample, by 32,640 B column slabs)
ROW_LAYOUTS = [
    ((84, 84), (1, 7056, 7168)),                  # a frame: one row
    ((84, 84, 4), (1, 28224, 28288)),             # a flat stack: one row
    ((11, 36, 36), (1, 14256, 14336)),            # a small sequence
    ((83, 84, 84), (83, 7056, 7168)),             # R2D2, frame mode
    ((80, 84, 84, 4), (80, 28224, 28288)),        # R2D2, stacked obs
    ((11, 60, 60), (11, 3600, 3712)),
]


@pytest.mark.parametrize("shape,want", ROW_LAYOUTS)
def test_row_layout_splits_only_items_wider_than_a_gather_fetches(
        shape, want):
    assert row_layout(shape) == want
    rows, _, row = want
    assert row <= GATHER_ROW_MAX_BYTES and row % 128 == 0
    # one row per item for as long as that row can be gathered whole
    assert (rows == 1) == (pad128(int(np.prod(shape)))
                           <= GATHER_ROW_MAX_BYTES)


def test_row_layout_refuses_a_slice_no_gather_fetches_whole():
    with pytest.raises(ValueError, match="wider than"):
        row_layout((4, 256, 256))


def _split_spec():
    return {"seq_frames": jax.ShapeDtypeStruct((11, 60, 60), jnp.uint8),
            "mask": jax.ShapeDtypeStruct((8,), jnp.float32)}


def _split_items(rng, n, lead=()):
    return {"seq_frames": jnp.asarray(
                rng.integers(0, 255, (*lead, n, 11, 60, 60)), jnp.uint8),
            "mask": jnp.asarray(rng.random((*lead, n, 8)), jnp.float32)}


def test_packer_roundtrip_of_a_split_leaf():
    spec = _split_spec()
    packer = PixelPacker(spec)
    assert packer.storage_spec(spec)["seq_frames"].shape == (3712,)
    assert packer.rows_per_item() == {"seq_frames": 11, "mask": 1}
    for lead in ((), (2,)):
        items = _split_items(np.random.default_rng(0), 5, lead)
        rows = packer.encode(items)
        assert rows["seq_frames"].shape == (*lead, 5 * 11, 3712)
        back = packer.decode({
            "seq_frames": rows["seq_frames"].reshape(*lead, 5, 11, 3712),
            "mask": rows["mask"]})
        np.testing.assert_array_equal(np.asarray(back["seq_frames"]),
                                      np.asarray(items["seq_frames"]))


@pytest.mark.parametrize("kind", ["prioritized", "uniform"])
def test_replay_with_a_split_leaf_returns_what_was_added(kind):
    """add (incl. a skip-to-head wrap), sample and read_region over a
    [capacity * rows, row] buffer give back the items of each slot."""
    cap, b = 8, 3
    replay = (PrioritizedReplay(cap, item_spec=_split_spec())
              if kind == "prioritized"
              else UniformReplayDevice(cap, item_spec=_split_spec()))
    state = replay.init()
    assert state.storage["seq_frames"].shape == (cap * 11, 3712)
    rng = np.random.default_rng(1)
    slots = {}
    for start in (0, 3, 0):            # the third add wraps to the head
        items = _split_items(rng, b)
        state = replay.add(state, items, jnp.ones(b))
        for i in range(b):
            slots[start + i] = jax.tree.map(lambda x: np.asarray(x)[i],
                                            items)
    got, idx, _ = replay.sample(state, jax.random.PRNGKey(0), 16)
    for j, slot in enumerate(np.asarray(idx)):
        for k in ("seq_frames", "mask"):
            np.testing.assert_array_equal(np.asarray(got[k])[j],
                                          slots[int(slot)][k])
    if kind == "prioritized":
        region, _ = replay.read_region(state, jnp.int32(2), 3)
        for j, slot in enumerate((2, 3, 4)):
            np.testing.assert_array_equal(
                np.asarray(region["seq_frames"])[j],
                slots[slot]["seq_frames"])


def test_lockstep_add_of_a_split_leaf_writes_every_shard():
    dp, cap, b = 2, 8, 2
    replay = PrioritizedReplay(cap, item_spec=_split_spec())
    state = jax.vmap(lambda _: replay.init())(jnp.arange(dp))
    items = _split_items(np.random.default_rng(2), b, (dp,))
    for _ in range(2):
        state = replay.add_lockstep(state, items, jnp.ones((dp, b)))
    # directed per-shard write: shard 0 at slot 5, shard 1 at slot 0
    state = replay.add_at_lockstep(state, items, jnp.ones((dp, b)),
                                   jnp.asarray([5, 0], jnp.int32))
    stored = np.asarray(state.storage["seq_frames"]).reshape(
        dp, cap, 11, 3712)[..., :3600].reshape(dp, cap, 11, 60, 60)
    want = np.asarray(items["seq_frames"])
    for d, slots in enumerate(((0, 2, 5), (0, 2))):
        for slot in slots:
            np.testing.assert_array_equal(stored[d, slot:slot + b],
                                          want[d])


def test_budget_prices_a_sequence_by_its_rows():
    """utils/hbm.py prices the row layout the replay allocates: 83 rows
    of 7,168 B per R2D2 sequence, not one row of 585,728 B."""
    cfg = get_config("r2d2")
    b = hbm.run_budget(cfg, (84, 84, 4), np.uint8, param_count=3_800_000)
    assert b.detail["seq_item_bytes"] == 83 * 7168 + 80 * 16 + 2 * 2048
    replay = PrioritizedReplay(8, item_spec={
        "seq_frames": jax.ShapeDtypeStruct((83, 84, 84), jnp.uint8)})
    rows = jax.eval_shape(replay.init).storage["seq_frames"]
    assert rows.shape == (8 * 83, 7168)


# ---------------------------------------------------------------------------
# skip-to-head ring writes


def test_ring_write_start_no_wrap_is_identity():
    for pos in (0, 4, 12):
        assert int(ring_write_start(jnp.int32(pos), 4, 16)) == pos


def test_ring_write_start_wrap_skips_to_head():
    assert int(ring_write_start(jnp.int32(14), 4, 16)) == 0
    assert int(ring_write_start(jnp.int32(15), 2, 16)) == 0


def _items(b, base):
    return {
        "x": jnp.arange(base, base + b, dtype=jnp.float32),
    }


def test_replay_skip_to_head_keeps_tree_storage_consistent():
    """A wrapping add writes at slot 0; every tree leaf must keep
    pointing at the item actually stored in its slot (the consistency
    the modular ring guaranteed)."""
    replay = PrioritizedReplay(capacity=8)
    state = replay.init({"x": jax.ShapeDtypeStruct((), jnp.float32)})
    # two adds of 3: pos 0 -> 3 -> 6; third add of 3 would wrap -> head
    for k in range(3):
        state = replay.add(state, _items(3, 10 * k),
                           jnp.full(3, float(k + 1)))
    assert int(state.pos) == 3  # skip-to-head: restarted at 0, +3
    stored = np.asarray(state.storage["x"])
    # adds land at 0, 3, then (skip) 0 again: slots 0..2 hold the third
    # add (overwrote the first), 3..5 the second, 6..7 never written
    np.testing.assert_array_equal(stored[0:3], [20.0, 21.0, 22.0])
    np.testing.assert_array_equal(stored[3:6], [10.0, 11.0, 12.0])
    from ape_x_dqn_tpu.ops import sum_tree
    leaves = np.asarray(sum_tree.leaves(state.tree))
    eps, alpha = replay.eps, replay.alpha
    np.testing.assert_allclose(leaves[0:3], (3.0 + eps) ** alpha, rtol=1e-5)
    np.testing.assert_allclose(leaves[3:6], (2.0 + eps) ** alpha, rtol=1e-5)
    # the skipped tail slots stay empty AND unsampleable (priority 0),
    # and size does NOT count them as filled (never-written slots would
    # otherwise be sampleable in uniform replay and inflate IS-weight N)
    np.testing.assert_array_equal(leaves[6:8], 0.0)
    assert int(state.size) == 6


def test_replay_block_dividing_capacity_matches_modular_ring():
    """When the block divides the capacity (every fixed-block staging),
    skip-to-head never fires and eviction is plain FIFO."""
    replay = PrioritizedReplay(capacity=8)
    state = replay.init({"x": jax.ShapeDtypeStruct((), jnp.float32)})
    for k in range(3):  # 12 items through an 8-ring in blocks of 4
        state = replay.add(state, _items(4, 10 * k),
                           jnp.ones(4))
    stored = np.asarray(state.storage["x"])
    np.testing.assert_array_equal(stored[0:4], [20.0, 21.0, 22.0, 23.0])
    np.testing.assert_array_equal(stored[4:8], [10.0, 11.0, 12.0, 13.0])
    assert int(state.pos) == 4 and int(state.size) == 8


def test_prioritized_replay_packs_pixel_items_transparently():
    """Pixel items round-trip through packed byte-row storage."""
    replay = PrioritizedReplay(capacity=16)
    spec = {
        "obs": jax.ShapeDtypeStruct((32, 32, 4), jnp.uint8),
        "action": jax.ShapeDtypeStruct((), jnp.int32),
    }
    state = replay.init(spec)
    assert state.storage["obs"].shape == (16, pad128(32 * 32 * 4))
    rng = np.random.default_rng(1)
    obs = jnp.asarray(rng.integers(0, 255, (4, 32, 32, 4)), jnp.uint8)
    items = {"obs": obs, "action": jnp.arange(4, dtype=jnp.int32)}
    state = replay.add(state, items, jnp.ones(4))
    got, idx, w = replay.sample(state, jax.random.key(0), 8)
    assert got["obs"].shape == (8, 32, 32, 4)
    # every sampled obs equals the stored item at its index
    for i, src in enumerate(np.asarray(idx)):
        np.testing.assert_array_equal(np.asarray(got["obs"][i]),
                                      np.asarray(obs[src]))


# ---------------------------------------------------------------------------
# HBM budget


def test_budget_pong_preset_fits_16g_chip():
    cfg = get_config("pong")
    b = hbm.run_budget(cfg, (84, 84, 4), np.uint8, param_count=1_700_000)
    # 2^20 transitions as byte-row frame ring: (2^20/16)*22 rows * 7168B
    assert b.capacity == 1 << 20
    frames = (1 << 20) // 16 * 22 * 7168
    assert b.replay_storage == frames + (1 << 20) * 16
    assert b.total < 15.75 * 1024 ** 3  # fits the v5e chip
    assert "TOTAL" in b.table()


def test_budget_r2d2_preset_fits_per_shard():
    cfg = get_config("r2d2")
    b = hbm.run_budget(cfg, (84, 84, 4), np.uint8, param_count=6_500_000)
    assert b.capacity == 16_384  # 65536 sequences over dp=4
    assert b.total < 15.75 * 1024 ** 3


def test_budget_atari57_preset_fits_per_shard():
    cfg = get_config("atari57_apex")
    b = hbm.run_budget(cfg, (84, 84, 4), np.uint8, param_count=1_700_000)
    assert b.capacity == 1 << 19  # 2M over dp=4
    assert b.total < 15.75 * 1024 ** 3


def test_check_hbm_fits_raises_loudly_when_oversized():
    cfg = get_config("pong")
    with pytest.raises(ValueError, match="GiB per device"):
        hbm.check_hbm_fits(cfg, (84, 84, 4), np.uint8,
                           hbm_bytes=4 * 1024 ** 3)  # pretend a 4GiB chip


def test_check_hbm_fits_silent_without_memory_stats():
    cfg = get_config("pong")
    # no hbm_bytes and a backend without memory stats -> returns budget
    b = hbm.check_hbm_fits(cfg, (84, 84, 4), np.uint8, hbm_bytes=None)
    assert b.total > 0


def test_frame_mode_predicate_shared():
    """sequence_frame_mode and frame_ring_mode are the SAME function
    object (packing.frame_mode) — the two modules alias one predicate,
    so single-frame-storage eligibility can never drift between the
    sequence and flat frame-ring paths."""
    from ape_x_dqn_tpu.replay.frame_ring import frame_ring_mode
    from ape_x_dqn_tpu.replay.packing import frame_mode
    from ape_x_dqn_tpu.replay.sequence import sequence_frame_mode

    assert sequence_frame_mode is frame_mode
    assert frame_ring_mode is frame_mode
    assert frame_mode("frame_ring", (84, 84, 4))
    assert not frame_mode("flat", (84, 84, 4))
    assert not frame_mode("frame_ring", (4,))


def test_replay_non_dividing_block_retires_tail_slots():
    """The default ActorConfig.ingest_batch=50 does not divide a
    power-of-two capacity, so skip-to-head wrap DOES fire on the flat
    ingest path (the docstring's 'never occurs' only covers the
    frame-ring/segment paths): up to block-1 tail slots are permanently
    retired — priority 0, never sampled, never counted in size — a
    bounded capacity loss, not a correctness hazard."""
    cap, block = 64, 50
    replay = PrioritizedReplay(capacity=cap)
    state = replay.init({"x": jax.ShapeDtypeStruct((), jnp.float32)})
    state = replay.add(state, _items(block, 0), jnp.ones(block))
    assert int(state.pos) == 50 and int(state.size) == 50
    # second block wraps: skip-to-head restarts at 0
    state = replay.add(state, _items(block, 100), jnp.ones(block))
    assert int(state.pos) == 50
    # tail slots 50..63 were retired, never filled: size stays 50
    assert int(state.size) == 50
    from ape_x_dqn_tpu.ops import sum_tree
    leaves = np.asarray(sum_tree.leaves(state.tree))
    np.testing.assert_array_equal(leaves[50:64], 0.0)
    # and retired slots are never sampled even over many draws
    _, idx, _ = replay.sample(state, jax.random.key(0), 512)
    assert np.asarray(idx).max() < 50
    # steady state: every further block lands at 0..49
    state = replay.add(state, _items(block, 200), jnp.ones(block))
    assert int(state.pos) == 50 and int(state.size) == 50
    stored = np.asarray(state.storage["x"])
    np.testing.assert_array_equal(stored[:50], np.arange(200, 250))
