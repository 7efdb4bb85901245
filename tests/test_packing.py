"""Tile-exact pixel packing + in-place ring-write semantics
(replay/packing.py) and the HBM budget check (utils/hbm.py).

These encode the round-5 HBM findings: on TPU a [cap, H, W] u8 buffer
pads 1.6x under the (32, 128) tile and XLA inserts a full-buffer
relayout copy in every gather/scatter program over it (measured 25.1GB
for the pong preset's 9.47GB ring — OOM), while packed rows +
dynamic_update_slice ring writes compile to temp=0 in-place graphs.
Since ISSUE 42 the packed rows are 32-bit words (a uint8 tile packs
four ROWS into a word and a row gather fetched four for one): the
bytes a store hands out are the bytes that went in, for every item
shape here. CPU tests can't see layouts, so they pin the SEMANTICS
(roundtrips, skip-to-head wrap, budget math); the compiled-memory
numbers live in PERF.md "HBM budget".
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.configs import get_config
from ape_x_dqn_tpu.replay.packing import (GATHER_ROW_MAX_BYTES, WORDS,
                                          PixelPacker, as_bytes, as_words,
                                          byte_transpose, pad128, pad_row,
                                          packable, ring_write_start,
                                          row_layout)
from ape_x_dqn_tpu.replay.prioritized import (PrioritizedReplay,
                                              UniformReplayDevice)
from ape_x_dqn_tpu.utils import hbm


# ---------------------------------------------------------------------------
# PixelPacker


def test_pad128():
    assert pad128(7056) == 7168
    assert pad128(128) == 128
    assert pad128(1) == 128


def test_pad_row_is_whole_lane_tiles_of_words():
    assert pad_row(7056) == 7168 == 14 * 128 * 4
    assert pad_row(28224) == 28672        # pad128 gives 28288 = 55.25 tiles
    assert pad_row(512) == 512 and pad_row(1) == 512


def test_words_hold_their_bytes_least_significant_first():
    rng = np.random.default_rng(0)
    rows = rng.integers(0, 256, (3, 5, 64), dtype=np.uint8)
    # the view's form (the frame ring's) and the strided one (the
    # packed store's encode) are the same words
    for strided in (False, True):
        words = as_words(jnp.asarray(rows), strided=strided)
        assert words.dtype == jnp.uint32 and words.shape == (3, 5, 16)
        np.testing.assert_array_equal(np.asarray(words),
                                      rows.view("<u4").reshape(3, 5, 16))
        np.testing.assert_array_equal(np.asarray(as_bytes(words)), rows)


def test_byte_transpose_turns_four_frames_words_into_four_stacks():
    rng = np.random.default_rng(1)
    frames = rng.integers(0, 256, (4, 6, 32), dtype=np.uint8)
    out = byte_transpose(list(as_words(jnp.asarray(frames))))
    # out[i] word k = pixel 4k + i of frames 0..3, in the order a
    # [..., stack] uint8 array keeps them
    for i in range(4):
        np.testing.assert_array_equal(
            np.asarray(as_bytes(out[i])).reshape(6, 8, 4),
            frames[:, :, i::4].transpose(1, 2, 0))


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
    assert stored["obs"].shape == (pad_row(84 * 84 * 4) // 4,)
    assert stored["obs"].dtype == jnp.uint32
    assert stored["action"].shape == ()  # untouched

    rng = np.random.default_rng(0)
    items = {
        "obs": jnp.asarray(rng.integers(0, 255, (5, 84, 84, 4)), jnp.uint8),
        "action": jnp.asarray(rng.integers(0, 4, 5), jnp.int32),
    }
    rows = packer.encode(items)
    assert rows["obs"].shape == (5, pad_row(84 * 84 * 4) // 4)
    # a one-row leaf has no word form to hand on: bytes and a reshape
    for back in (packer.decode(rows), packer.decode(rows, words=True)):
        assert set(back) == {"obs", "action"}
        assert back["obs"].dtype == jnp.uint8
        np.testing.assert_array_equal(np.asarray(back["obs"]),
                                      np.asarray(items["obs"]))
        np.testing.assert_array_equal(np.asarray(back["action"]),
                                      np.asarray(items["action"]))


# an item wider than a TPU gather fetches whole is stored one row per
# leading-axis slice (ISSUE 26: an R2D2 sequence as ONE 585,728 B row
# made XLA copy the whole replay per sample, by 32,640 B column slabs)
ROW_LAYOUTS = [
    ((84, 84), (1, 7056, 7168)),                  # a frame: one row
    ((84, 84, 4), (1, 28224, 28672)),             # a flat stack: one row
    ((11, 36, 36), (1, 14256, 14336)),            # a small sequence
    ((83, 84, 84), (83, 7056, 7168)),             # R2D2, frame mode
    ((80, 84, 84, 4), (80, 28224, 28672)),        # R2D2, stacked obs
    ((11, 60, 60), (11, 3600, 4096)),
    # 32,300 B is a row under pad128 (32,384) and past the widest
    # gather once padded to whole tiles of words (32,768): split
    ((4, 85, 95), (4, 8075, 8192)),
]


@pytest.mark.parametrize("shape,want", ROW_LAYOUTS)
def test_row_layout_splits_only_items_wider_than_a_gather_fetches(
        shape, want):
    assert row_layout(shape) == want
    rows, _, row = want
    assert row <= GATHER_ROW_MAX_BYTES and row % 512 == 0
    # one row per item for as long as that row can be gathered whole
    assert (rows == 1) == (pad_row(int(np.prod(shape)))
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
    assert packer.storage_spec(spec)["seq_frames"] == \
        jax.ShapeDtypeStruct((1024,), jnp.uint32)   # 3,600 B -> 4,096
    assert packer.rows_per_item() == {"seq_frames": 11, "mask": 1}
    for lead in ((), (2,)):
        items = _split_items(np.random.default_rng(0), 5, lead)
        rows = packer.encode(items)
        assert rows["seq_frames"].shape == (*lead, 5 * 11, 1024)
        assert rows["seq_frames"].dtype == jnp.uint32
        sampled = {
            "seq_frames": rows["seq_frames"].reshape(*lead, 5, 11, 1024),
            "mask": rows["mask"]}
        back = packer.decode(sampled)
        assert set(back) == {"seq_frames", "mask"}
        np.testing.assert_array_equal(np.asarray(back["seq_frames"]),
                                      np.asarray(items["seq_frames"]))
        # what a sample hands on: the same bytes, and the rows as
        # they were gathered beside them
        both = packer.decode(sampled, words=True)
        assert set(both) == {"seq_frames", "mask", "seq_frames" + WORDS}
        np.testing.assert_array_equal(np.asarray(both["seq_frames"]),
                                      np.asarray(items["seq_frames"]))
        assert both["seq_frames" + WORDS] is sampled["seq_frames"]


@pytest.mark.parametrize("kind", ["prioritized", "uniform"])
def test_replay_with_a_split_leaf_returns_what_was_added(kind):
    """add (incl. a skip-to-head wrap), sample and read_region over a
    [capacity * rows, row] buffer give back the items of each slot."""
    cap, b = 8, 3
    replay = (PrioritizedReplay(cap, item_spec=_split_spec())
              if kind == "prioritized"
              else UniformReplayDevice(cap, item_spec=_split_spec()))
    state = replay.init()
    assert state.storage["seq_frames"].shape == (cap * 11, 1024)
    assert state.storage["seq_frames"].dtype == jnp.uint32
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


# every item shape this file covers, by how the packer lays it out
ITEM_SHAPES = {
    "one_row_padded": (84, 84, 4),        # 28,224 B in a 28,672 B row
    "one_row_exact": (32, 32, 4),         # 4,096 B: whole tiles, no pad
    "one_row_odd_bytes": (3, 37, 37),     # 4,107 B: the last word is pad
    "one_row_sequence": (11, 36, 36),
    "split_padded": (11, 60, 60),         # 11 rows of 3,600 B in 4,096
    "split_exact": (9, 64, 64),           # 9 rows of 4,096 B
    "split_odd_bytes": (9, 61, 61),       # 9 rows of 3,721 B
}


@pytest.mark.parametrize("kind", ["prioritized", "uniform", "lockstep"])
@pytest.mark.parametrize("name", list(ITEM_SHAPES))
def test_add_then_sample_returns_the_very_bytes_that_went_in(name, kind):
    """Word rows are a storage form: for one-row leaves, split leaves,
    rows that need padding to 512 B and [dp, b] leads, a sample is
    the bytes of the slot it drew, uint8 in the item's own shape."""
    shape = ITEM_SHAPES[name]
    spec = {"pixels": jax.ShapeDtypeStruct(shape, jnp.uint8),
            "tag": jax.ShapeDtypeStruct((), jnp.int32)}
    cap, b, dp = 8, 4, 2
    rng = np.random.default_rng(3)
    lead = (dp,) if kind == "lockstep" else ()
    blocks = [{"pixels": rng.integers(0, 256, (*lead, b, *shape),
                                      dtype=np.uint8),
               "tag": rng.integers(0, 99, (*lead, b)).astype(np.int32)}
              for _ in range(2)]
    stored = {k: np.concatenate([blk[k] for blk in blocks], axis=len(lead))
              for k in spec}
    if kind == "lockstep":
        replay = PrioritizedReplay(cap, item_spec=spec)
        state = jax.vmap(lambda _: replay.init())(jnp.arange(dp))
        for blk in blocks:
            state = replay.add_lockstep(state, blk, jnp.ones((dp, b)))
        got, idx, _ = jax.vmap(
            lambda st, key: replay.sample_items(st, key, 16))(
            state, jax.random.split(jax.random.PRNGKey(0), dp))
        want = np.stack([stored["pixels"][d][np.asarray(idx[d])]
                         for d in range(dp)])
    else:
        replay = (PrioritizedReplay(cap, item_spec=spec)
                  if kind == "prioritized"
                  else UniformReplayDevice(cap, item_spec=spec))
        state = replay.init()
        for blk in blocks:
            state = replay.add(state, blk, jnp.ones(b))
        got, idx, _ = replay.sample(state, jax.random.PRNGKey(0), 16)
        want = stored["pixels"][np.asarray(idx)]
    rows, _, row = row_layout(shape)
    assert state.storage["pixels"].dtype == jnp.uint32
    assert state.storage["pixels"].shape == (*lead, cap * rows, row // 4)
    assert got["pixels"].dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(got["pixels"]), want)
    # a split leaf's rows go on beside it as they were gathered
    assert ("pixels" + WORDS in got) == (rows > 1)
    if rows > 1:
        np.testing.assert_array_equal(
            np.asarray(as_bytes(got["pixels" + WORDS]))[..., :row_layout(
                shape)[1]].reshape(want.shape), want)


@pytest.mark.parametrize("chunks", [1, 2, 4])
def test_a_draw_gathered_chunk_by_chunk_is_the_items_of_its_indices(chunks):
    """`sample_items(chunks=K)` gathers each chunk's rows on its own:
    what comes back is still item idx[i] at position i, every leaf."""
    replay = PrioritizedReplay(8, item_spec=_split_spec())
    items = _split_items(np.random.default_rng(4), 8)
    state = replay.add(replay.init(), items,
                       jnp.linspace(0.1, 2.0, 8, dtype=jnp.float32))
    got, idx, _ = jax.jit(
        lambda st, key: replay.sample_items(st, key, 16, chunks))(
        state, jax.random.PRNGKey(5))
    for k in ("seq_frames", "mask"):
        np.testing.assert_array_equal(
            np.asarray(got[k]), np.asarray(items[k])[np.asarray(idx)])
    np.testing.assert_array_equal(
        np.asarray(as_bytes(got["seq_frames" + WORDS]))[..., :3600],
        np.asarray(items["seq_frames"])[np.asarray(idx)].reshape(16, 11, -1))


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
    stored = np.asarray(state.storage["seq_frames"]).view(np.uint8).reshape(
        dp, cap, 11, 4096)[..., :3600].reshape(dp, cap, 11, 60, 60)
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
    assert rows.shape == (8 * 83, 1792) and rows.dtype == jnp.uint32


def test_the_r2d2_cell_stores_word_rows_and_the_budget_prices_them():
    """`benchmarks/configs/r2d2_1chip.json`'s store: 16,384 sequences
    of 83 frame rows, uint32 [capacity * 83, 1,792] — the 7,168 B rows
    it held as bytes — and utils/hbm.py prices exactly the bytes the
    replay allocates."""
    import json
    import pathlib

    from ape_x_dqn_tpu.replay.sequence import (sequence_frame_mode,
                                               sequence_item_spec)
    from ape_x_dqn_tpu.runtime.train import apply_overrides

    cell = json.loads((pathlib.Path(__file__).parents[1] / "benchmarks"
                       / "configs" / "r2d2_1chip.json").read_text())
    cfg = apply_overrides(get_config(cell["preset"]), cell["overrides"])
    obs_shape = tuple(cell["sizes"]["frame"])
    assert sequence_frame_mode(cfg.replay.storage, obs_shape)
    spec = sequence_item_spec(obs_shape, np.uint8, cfg.replay.seq_length,
                              cfg.network.lstm_size, frame_mode=True)
    replay = PrioritizedReplay(cfg.replay.capacity, item_spec=spec)
    storage = jax.eval_shape(replay.init).storage
    assert storage["seq_frames"] == jax.ShapeDtypeStruct(
        (16_384 * 83, 1_792), jnp.uint32)
    allocated = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                    for x in jax.tree.leaves(storage))
    priced, _, capacity, _ = hbm.replay_budget(cfg, obs_shape, np.uint8)
    assert capacity == 16_384 and priced == allocated
    assert priced == 16_384 * (83 * 7_168 + 80 * 16 + 2 * 512 * 4)


@pytest.mark.parametrize("name", ["one_row_padded", "split_padded",
                                  "split_odd_bytes"])
def test_a_checkpoint_holds_the_byte_rows_the_store_held_as_bytes(name):
    """A replay-bearing checkpoint (layout v3) holds a packed leaf as
    uint8 [capacity * rows, pad128(row bytes)]; the word rows leave
    the device as exactly that and come back as they were."""
    shape = ITEM_SHAPES[name]
    rows, nbytes, _ = row_layout(shape)
    spec = {"pixels": jax.ShapeDtypeStruct(shape, jnp.uint8),
            "tag": jax.ShapeDtypeStruct((), jnp.int32)}
    replay = PrioritizedReplay(8, item_spec=spec)
    rng = np.random.default_rng(9)
    items = {"pixels": rng.integers(0, 256, (8, *shape), dtype=np.uint8),
             "tag": np.arange(8, dtype=np.int32)}
    state = replay.add(replay.init(), items, jnp.ones(8))
    host = jax.tree.map(np.asarray, state.storage)
    on_disk = replay.checkpoint_rows(host)
    want = np.zeros((8 * rows, pad128(nbytes)), np.uint8)
    want[:, :nbytes] = items["pixels"].reshape(8 * rows, nbytes)
    assert on_disk["pixels"].dtype == np.uint8
    np.testing.assert_array_equal(on_disk["pixels"], want)
    np.testing.assert_array_equal(on_disk["tag"], items["tag"])
    back = replay.checkpoint_rows(on_disk, restore=True)
    for k in host:
        assert back[k].dtype == host[k].dtype and \
            back[k].shape == host[k].shape, k
        np.testing.assert_array_equal(back[k], host[k], err_msg=k)


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
    """Pixel items round-trip through packed word-row storage."""
    replay = PrioritizedReplay(capacity=16)
    spec = {
        "obs": jax.ShapeDtypeStruct((32, 32, 4), jnp.uint8),
        "action": jax.ShapeDtypeStruct((), jnp.int32),
    }
    state = replay.init(spec)
    assert state.storage["obs"].shape == (16, pad_row(32 * 32 * 4) // 4)
    assert state.storage["obs"].dtype == jnp.uint32
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
