"""Tiered cold replay (replay/cold_store.py + the replay-layer hooks):

- bitwise round-trip parity: a region evicted through
  evict_plan -> read_region -> cold_pack -> ColdStore -> recall ->
  restage -> add lands transitions bit-identical to the never-evicted
  originals, on BOTH storage layouts (frame-ring segment packer and
  the flat PixelPacker byte-row packer)
- priority-mass eviction picks the lowest-mass contiguous region, and
  the default (cold off) add keeps blind FIFO — the tier changes
  nothing unless switched on
- ColdStore admission: mass-ordered displacement, door drops, the
  never-inflate compression-ratio floor
- ReplayConfig.cold_tier_* validation (guided errors, satellite 6)
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.configs import ReplayConfig
from ape_x_dqn_tpu.replay import cold_store as cold_store_mod
from ape_x_dqn_tpu.replay.cold_store import ColdStore, codec_status
from ape_x_dqn_tpu.replay.frame_ring import (FrameRingReplay,
                                             frame_segment_spec)
from ape_x_dqn_tpu.replay.prioritized import PrioritizedReplay
from ape_x_dqn_tpu.runtime.learner import transition_item_spec

OBS_SHAPE = (84, 84, 4)


def _ring():
    # capacity 64 transitions, B=8 -> 8 segments
    return FrameRingReplay(64, seg_transitions=8, n_step=3,
                           obs_shape=OBS_SHAPE)


def _seg_batch(r, g, rng, compressible=True):
    """g staging segments; compressible frames exercise the delta path
    (consecutive frames differ in a few pixels, like real Atari)."""
    if compressible:
        base = rng.integers(0, 255, (84, 84)).astype(np.uint8)
        frames = np.broadcast_to(base, (g, r.F, 84, 84)).copy()
        frames[:, :, ::7, ::11] = rng.integers(
            0, 255, frames[:, :, ::7, ::11].shape)
    else:
        frames = rng.integers(0, 255, (g, r.F, 84, 84)).astype(np.uint8)
    return {
        "seg_frames": frames.astype(np.uint8),
        "action": rng.integers(0, 18, (g, r.B)).astype(np.int32),
        "reward": rng.standard_normal((g, r.B)).astype(np.float32),
        "discount": np.full((g, r.B), 0.99, np.float32),
        "next_off": rng.integers(1, 4, (g, r.B)).astype(np.int32),
    }


def _flat_batch(n, rng):
    return {
        "obs": rng.integers(0, 255, (n, *OBS_SHAPE)).astype(np.uint8),
        "action": rng.integers(0, 18, (n,)).astype(np.int32),
        "reward": rng.standard_normal((n,)).astype(np.float32),
        "next_obs": rng.integers(0, 255, (n, *OBS_SHAPE)).astype(np.uint8),
        "discount": np.full((n,), 0.99, np.float32),
    }


def _gather_all(r, state, idx):
    return jax.tree.map(np.asarray, r._gather(state, jnp.asarray(idx)))


# -- bitwise round-trip parity (the tentpole invariant) --------------------


def test_frame_ring_cold_round_trip_bitwise():
    """Evict the lowest-mass segment through the full cold cycle and
    restage it into a SECOND ring: every reconstructed transition
    (obs/next_obs stacks included) is bit-identical to sampling the
    original ring at the original slots."""
    rng = np.random.default_rng(0)
    r = _ring()
    st = r.init()
    g = 2  # eviction block: 2 segments, like segs_per_add=2 staging
    tds = [0.7, 0.05, 0.9, 0.4]  # block starting at seg 2 is lightest
    batches = [_seg_batch(r, g, rng) for _ in tds]
    for b, td in zip(batches, tds):
        st = r.add(st, b, np.full((g, r.B), td, np.float32))
    seg0 = int(r.evict_plan(st, g))
    assert seg0 == 2  # the td=0.05 block (segments 2,3)
    items, pri = r.read_region(st, jnp.int32(seg0), g)
    items = jax.tree.map(np.asarray, items)
    pri = np.asarray(pri)

    cold = ColdStore(frame_segment_spec(r.B, r.n, OBS_SHAPE, np.uint8),
                     capacity_transitions=1024, unit_items=r.B,
                     ptail=(r.B,))
    assert cold.put(items, pri, live=int((pri > 0).sum())) == "stored"
    [back] = cold.recall(1)
    # payload round trip is exact, priorities included
    for k in items:
        assert back[k].dtype == items[k].dtype, k
        np.testing.assert_array_equal(back[k], items[k], err_msg=k)
    np.testing.assert_array_equal(back["priorities"], pri)

    # restage into a fresh ring through the normal add path (the same
    # graph add_many unrolls), with the stored mass inverted to |td|
    td_back = np.maximum(
        np.asarray(back["priorities"]) ** (1.0 / r.alpha) - r.eps, 0.0
    ).astype(np.float32)
    r2 = _ring()
    st2 = r2.add(r2.init(),
                 {k: v for k, v in back.items() if k != "priorities"},
                 td_back)
    idx_orig = seg0 * r.B + np.arange(g * r.B)
    idx_new = np.arange(g * r.B)
    got = _gather_all(r2, st2, idx_new)
    want = _gather_all(r, st, idx_orig)
    for k in want:
        assert got[k].dtype == want[k].dtype, k
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)
    # restaged priorities match eviction-time mass (float round trip
    # through the alpha inversion, so allclose rather than bit-equal)
    np.testing.assert_allclose(
        np.asarray(st2.tree[r2.capacity:r2.capacity + g * r.B]),
        pri.reshape(-1), rtol=1e-5)


def test_flat_cold_round_trip_bitwise():
    """Same invariant on the flat layout: the PixelPacker byte-row
    storage decodes through read_region, survives the cold codec, and
    restages bit-identically."""
    rng = np.random.default_rng(1)
    spec = transition_item_spec(OBS_SHAPE, np.uint8)
    r = PrioritizedReplay(16, item_spec=spec)
    st = r.init()
    blocks = [_flat_batch(4, rng) for _ in range(4)]
    tds = [0.6, 0.8, 0.02, 0.5]  # block 2 is lightest
    for b, td in zip(blocks, tds):
        st = r.add(st, b, np.full((4,), td, np.float32))
    start = int(r.evict_plan(st, 4))
    assert start == 8
    items, pri = r.read_region(st, jnp.int32(start), 4)
    items = jax.tree.map(np.asarray, items)
    pri = np.asarray(pri)
    for k in blocks[2]:  # read_region already round-trips the packer
        np.testing.assert_array_equal(items[k], blocks[2][k], err_msg=k)

    cold = ColdStore(spec, capacity_transitions=64)
    assert cold.put(items, pri, live=4) == "stored"
    [back] = cold.recall(1)
    td_back = np.maximum(
        np.asarray(back["priorities"]) ** (1.0 / r.alpha) - r.eps, 0.0
    ).astype(np.float32)
    r2 = PrioritizedReplay(16, item_spec=spec)
    st2 = r2.add(r2.init(),
                 {k: v for k, v in back.items() if k != "priorities"},
                 td_back)
    got, _ = r2.read_region(st2, jnp.int32(0), 4)
    for k in blocks[2]:
        a = np.asarray(got[k])
        assert a.dtype == blocks[2][k].dtype, k
        np.testing.assert_array_equal(a, blocks[2][k], err_msg=k)


def _sequence_batch(n, rng):
    return {
        "seq_frames": rng.integers(0, 255, (n, 11, 60, 60)).astype(np.uint8),
        "mask": rng.random((n, 8)).astype(np.float32),
    }


# SHA-256[:16] of the cold segment the PARENT of ISSUE 42 wrote for
# these seeds, when the packed store's rows were bytes
COLD_SEGMENTS = {
    # one row per item, padded 28,224 -> 28,672 B
    "flat": (lambda: transition_item_spec(OBS_SHAPE, np.uint8),
             _flat_batch, "dab4852d639073a2"),
    # a split leaf: 11 rows of 3,600 B, padded to 4,096
    "sequence": (lambda: {
        "seq_frames": jax.ShapeDtypeStruct((11, 60, 60), jnp.uint8),
        "mask": jax.ShapeDtypeStruct((8,), jnp.float32)},
        _sequence_batch, "c2d7f9d6420188cf"),
}


@pytest.mark.parametrize("case", list(COLD_SEGMENTS))
def test_a_cold_segment_holds_the_bytes_it_held_as_byte_rows(case):
    """The packed store's rows are 32-bit words on the device (ISSUE
    42); what leaves it is bytes: the segment a region serialises to
    is the one its host items serialise to, and the one the parent
    wrote."""
    import hashlib

    from ape_x_dqn_tpu.replay.packing import cold_pack, cold_plan

    make_spec, make_batch, parents = COLD_SEGMENTS[case]
    spec = make_spec()
    rng = np.random.default_rng(7)
    r = PrioritizedReplay(16, item_spec=spec)
    st = r.init()
    blocks = [make_batch(4, rng) for _ in range(3)]
    for b in blocks:
        st = r.add(st, b, np.full((4,), 0.5, np.float32))
    assert all(leaf.dtype != jnp.uint8
               for leaf in jax.tree.leaves(st.storage))
    items, pri = r.read_region(st, jnp.int32(4), 4)
    items = {**jax.tree.map(np.asarray, items), "priorities": np.asarray(pri)}
    for k, v in blocks[1].items():
        assert items[k].dtype == v.dtype, k
    plan = cold_plan(spec)
    payload, raw = cold_pack(items, plan)
    assert (payload, raw) == cold_pack(
        {**blocks[1], "priorities": np.asarray(pri)}, plan)
    assert hashlib.sha256(payload).hexdigest()[:16] == parents


# -- eviction placement + the cold-off FIFO pin ----------------------------


def test_evict_plan_picks_lowest_mass_region():
    rng = np.random.default_rng(2)
    r = _ring()
    st = r.init()
    for td in (0.3, 0.6, 0.01, 0.02, 0.9, 0.8, 0.7, 0.5):
        st = r.add(st, _seg_batch(r, 1, rng),
                   np.full((1, r.B), td, np.float32))
    # window of 2 contiguous segments with least mass: segments 2+3
    assert int(r.evict_plan(st, 2)) == 2
    # flat analog
    spec = transition_item_spec(OBS_SHAPE, np.uint8)
    fr = PrioritizedReplay(16, item_spec=spec)
    fst = fr.init()
    for td in (0.5, 0.01, 0.9, 0.7):
        fst = fr.add(fst, _flat_batch(4, rng), np.full((4,), td))
    assert int(fr.evict_plan(fst, 4)) == 4


def test_cold_off_add_stays_fifo():
    """With the tier off nothing consults priority mass: a full ring's
    next default add overwrites the FIFO cursor position even when a
    far lower-mass region exists — the pre-PR behavior, bit for bit."""
    rng = np.random.default_rng(3)
    r = _ring()
    st = r.init()
    batches = [_seg_batch(r, 1, rng) for _ in range(8)]
    tds = (0.9, 0.001, 0.9, 0.9, 0.9, 0.9, 0.9, 0.9)  # seg 1 lightest
    for b, td in zip(batches, tds):
        st = r.add(st, b, np.full((1, r.B), td, np.float32))
    assert int(st.pos) == 0 and int(st.size) == r.capacity
    fresh = _seg_batch(r, 1, rng)
    st = r.add(st, fresh, np.full((1, r.B), 0.5, np.float32))
    # FIFO landed on segment 0, NOT on the lowest-mass segment 1
    got0, _ = r.read_region(st, jnp.int32(0), 1)
    got1, _ = r.read_region(st, jnp.int32(1), 1)
    for k in fresh:
        np.testing.assert_array_equal(np.asarray(got0[k]), fresh[k],
                                      err_msg=k)
        np.testing.assert_array_equal(np.asarray(got1[k]), batches[1][k],
                                      err_msg=k)


# -- ColdStore admission policy --------------------------------------------


def _tiny_store(cap=16):
    spec = {"x": jax.ShapeDtypeStruct((4, 1024), np.uint8)}
    return ColdStore(spec, capacity_transitions=cap, unit_items=4,
                     ptail=(4,))


def _tiny_seg(rng, mass):
    items = {"x": rng.integers(0, 4, (1, 4, 1024)).astype(np.uint8)}
    pri = np.full((1, 4), mass, np.float32)
    return items, pri


def test_cold_store_mass_ordered_displacement_and_door_drop():
    rng = np.random.default_rng(4)
    cs = _tiny_store(cap=16)  # 4 segments of 4 live transitions
    for mass in (0.4, 0.2, 0.8, 0.6):
        items, pri = _tiny_seg(rng, mass)
        assert cs.put(items, pri, live=4) == "stored"
    assert len(cs) == 4 and cs.transitions == 16
    # lighter than the lightest stored -> dropped at the door
    items, pri = _tiny_seg(rng, 0.1)
    assert cs.put(items, pri, live=4) == "dropped"
    assert cs.dropped == 1 and len(cs) == 4
    # heavier -> displaces the lightest (mass 0.2)
    items, pri = _tiny_seg(rng, 0.9)
    assert cs.put(items, pri, live=4) == "stored"
    assert cs.displaced == 1 and len(cs) == 4
    # recall pops highest mass first: 0.9*4, then 0.8*4
    [a] = cs.recall(1)
    assert a["priorities"][0, 0] == np.float32(0.9)
    [b] = cs.recall(1)
    assert b["priorities"][0, 0] == np.float32(0.8)
    assert cs.recalled == 2
    # all-dead regions are dropped without storing
    items, pri = _tiny_seg(rng, 0.0)
    assert cs.put(items, pri, live=0) == "dropped"
    # door closure bookkeeping is the caller's (driver) denomination;
    # the store's own counters close in segment units
    assert cs.stored == 5 and cs.dropped == 2


def test_cold_store_compression_ratio_floor():
    """Incompressible data hits the per-leaf never-inflate guard (raw
    mode): the resident ratio never reads below 1.0."""
    rng = np.random.default_rng(5)
    spec = {"x": jax.ShapeDtypeStruct((4, 4096), np.uint8)}
    cs = ColdStore(spec, capacity_transitions=64, unit_items=4,
                   ptail=(4,))
    items = {"x": rng.integers(0, 256, (2, 4, 4096)).astype(np.uint8)}
    pri = np.full((2, 4), 0.5, np.float32)
    assert cs.put(items, pri, live=8) == "stored"
    assert cs.compression_ratio() >= 1.0
    # payload may exceed raw only by the constant per-leaf framing
    assert cs.bytes_compressed <= cs.bytes_raw + 9 * 2


def test_cold_tier_holds_eight_rings_at_an_eighth_of_the_bytes():
    """The tier's capacity criterion, at a 4,096-transition ring of
    16-transition segments evicted 512 transitions at a time: the cold
    store ends up holding 8x the ring's transitions, the door having
    dropped none of them, at under 1/8 of the bytes per transition the
    ring's device state costs (frames that share a base image with
    sparse per-frame noise, like emulator play)."""
    rng = np.random.default_rng(7)
    capacity, block_units = 4096, 32
    r = FrameRingReplay(capacity, seg_transitions=16, n_step=3,
                        obs_shape=OBS_SHAPE)
    ring_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(r.init()))
    cold = ColdStore(frame_segment_spec(r.B, r.n, OBS_SHAPE, np.uint8),
                     capacity_transitions=16 * capacity, unit_items=r.B,
                     ptail=(r.B,), compress_level=1)
    block = _seg_batch(r, block_units, rng)
    pri = rng.uniform(0.1, 2.0, (block_units, r.B)).astype(np.float32)
    evicted = 0
    while cold.transitions < 8 * capacity:
        assert cold.put(block, pri, live=block_units * r.B) == "stored"
        evicted += block_units * r.B
    assert cold.transitions == evicted == 8 * capacity
    assert cold.dropped == 0 and cold.displaced == 0
    cold_bpt = cold.bytes_compressed / cold.transitions
    assert cold_bpt < (ring_bytes / capacity) / 8, cold_bpt


def test_codec_status_reports_available():
    ok, detail = codec_status()
    assert ok
    assert detail in ("native", "numpy-fallback")


# -- ReplayConfig validation (satellite 6) ---------------------------------


def test_replay_config_rejects_negative_cold_capacity():
    with pytest.raises(ValueError, match="cold_tier_capacity"):
        ReplayConfig(cold_tier_capacity=-1)


def test_replay_config_guided_error_without_codec(monkeypatch):
    monkeypatch.setattr(cold_store_mod, "codec_status",
                        lambda: (False, "ImportError: no comm.native"))
    with pytest.raises(ValueError, match="numpy fallback"):
        ReplayConfig(cold_tier_capacity=1 << 16)


def test_replay_config_cold_defaults_off():
    cfg = ReplayConfig()
    assert cfg.cold_tier_capacity == 0
    assert dataclasses.replace(cfg).cold_tier_capacity == 0


# -- disk-spill hook (PR 16: ColdStore -> replay/disk_store.py) ------------


class _FakeSpill:
    """Records offers; configurable accept so queue-full refusal paths
    are testable without a real writeback thread."""

    def __init__(self, accept=True):
        self.offers = []
        self.accept = accept

    def offer(self, seg):
        self.offers.append(seg)
        return self.accept


def _spill_store(cap=16, accept=True):
    spec = {"x": jax.ShapeDtypeStruct((4, 1024), np.uint8)}
    spill = _FakeSpill(accept)
    cs = ColdStore(spec, capacity_transitions=cap, unit_items=4,
                   ptail=(4,), spill=spill)
    return cs, spill


def _fill(cs, rng, masses):
    for mass in masses:
        items, pri = _tiny_seg(rng, mass)
        assert cs.put(items, pri, live=4) == "stored"


def test_cold_spill_door_dropped_candidate_is_offered():
    rng = np.random.default_rng(6)
    cs, spill = _spill_store(cap=16)
    _fill(cs, rng, (0.4, 0.5, 0.6, 0.7))
    items, pri = _tiny_seg(rng, 0.1)  # lighter than everything stored
    assert cs.put(items, pri, live=4) == "dropped"
    assert cs.dropped == 1 and cs.spilled == 1
    [seg] = spill.offers
    assert seg.mass_sum == pytest.approx(0.1 * 4)
    assert seg.live == 4 and len(seg.payload) > 0


def test_cold_spill_displacement_victims_are_offered():
    rng = np.random.default_rng(7)
    cs, spill = _spill_store(cap=16)
    _fill(cs, rng, (0.2, 0.5, 0.6, 0.7))
    items, pri = _tiny_seg(rng, 0.9)  # displaces the 0.2 segment
    assert cs.put(items, pri, live=4) == "stored"
    assert cs.displaced == 1 and cs.spilled == 1
    [victim] = spill.offers
    assert victim.mass_sum == pytest.approx(0.2 * 4)


def test_cold_spill_refusal_not_counted_as_spilled():
    rng = np.random.default_rng(8)
    cs, spill = _spill_store(cap=16, accept=False)
    _fill(cs, rng, (0.4, 0.5, 0.6, 0.7))
    items, pri = _tiny_seg(rng, 0.1)
    assert cs.put(items, pri, live=4) == "dropped"
    assert len(spill.offers) == 1  # offered, refused (queue full)
    assert cs.spilled == 0


def test_cold_spill_all_dead_regions_never_offered():
    rng = np.random.default_rng(9)
    cs, spill = _spill_store(cap=16)
    items, pri = _tiny_seg(rng, 0.0)
    assert cs.put(items, pri, live=0) == "dropped"
    assert spill.offers == []  # zero mass: nothing worth disk bytes


def test_disk_rung_retains_eight_cold_tiers_without_errors(tmp_path):
    """The disk rung's retention criterion through the real spill
    chain (ColdStore door -> DiskStore writeback), at a cold tier of
    8,192 transitions fed 512-transition eviction blocks: every door
    loser is offered, the disk ends up holding 8x the cold tier's
    capacity with no I/O error and no corrupt segment, and the
    heaviest segments read back through their CRCs."""
    from ape_x_dqn_tpu.replay.disk_store import DiskStore

    rng = np.random.default_rng(7)
    r = FrameRingReplay(4096, seg_transitions=16, n_step=3,
                        obs_shape=OBS_SHAPE)
    cold_cap, block_units = 8192, 32
    target = 8 * cold_cap
    disk = DiskStore(str(tmp_path / "disk"), 2 * target, queue_depth=16)
    cold = ColdStore(frame_segment_spec(r.B, r.n, OBS_SHAPE, np.uint8),
                     capacity_transitions=cold_cap, unit_items=r.B,
                     ptail=(r.B,), compress_level=1, spill=disk)
    block = _seg_batch(r, block_units, rng)
    live = block_units * r.B
    try:
        puts = 0
        while disk.transitions < target:
            pri = np.full((block_units, r.B), rng.uniform(0.1, 2.0),
                          np.float32)
            cold.put(block, pri, live=live)
            puts += 1
            if disk.stats()["queue_full"]:
                disk.drain(timeout=60.0)  # offer() never waits; we do
            assert puts <= 4 * (target // live + cold_cap // live), puts
        disk.drain(timeout=60.0)
        stats = disk.stats()
        assert stats["transitions"] >= target
        assert stats["io_errors"] == 0
        assert stats["corrupt_segments"] == 0
        assert cold.spilled * live >= stats["transitions"]
        promoted = disk.promote(4, floor=0.0)
        assert len(promoted) == 4 and all(s.live == live
                                          for s in promoted)
        assert disk.stats()["corrupt_segments"] == 0
    finally:
        disk.close()


def test_put_segment_door_without_touching_eviction_counters():
    from ape_x_dqn_tpu.replay.cold_store import ColdSegment
    rng = np.random.default_rng(10)
    cs, spill = _spill_store(cap=16)
    _fill(cs, rng, (0.3, 0.5, 0.6, 0.7))
    stored0, dropped0 = cs.stored, cs.dropped
    # a promoted segment heavier than the lightest resident: admitted,
    # victim spills back down, stored/dropped stay untouched (the
    # driver closure is denominated in ring evictions, not promotions)
    heavy = ColdSegment(b"promoted-bytes", 1, 4, 48, 0.4 * 4, 0.4, 7)
    assert cs.put_segment(heavy) == "stored"
    assert cs.displaced == 1
    [victim] = spill.offers
    assert victim.mass_sum == pytest.approx(0.3 * 4)
    # a promoted segment lighter than the floor: dropped, NOT
    # re-spilled (ping-pong prevention)
    light = ColdSegment(b"light-bytes", 1, 4, 48, 0.01, 0.01, 8)
    assert cs.put_segment(light) == "dropped"
    assert len(spill.offers) == 1
    assert (cs.stored, cs.dropped) == (stored0, dropped0)


def test_displacement_floor_tracks_lightest_at_capacity():
    rng = np.random.default_rng(11)
    cs, _ = _spill_store(cap=16)
    assert cs.displacement_floor() == 0.0
    _fill(cs, rng, (0.4, 0.6))
    assert cs.displacement_floor() == 0.0  # below capacity
    _fill(cs, rng, (0.5, 0.7))
    assert cs.displacement_floor() == pytest.approx(0.4 * 4)


# -- ReplayConfig disk-knob validation (PR 16) -----------------------------


def test_replay_config_rejects_negative_disk_capacity():
    with pytest.raises(ValueError, match="cold_tier_disk_capacity"):
        ReplayConfig(cold_tier_disk_capacity=-1)


def test_replay_config_disk_requires_ram_tier():
    with pytest.raises(ValueError, match="cold_tier_capacity > 0"):
        ReplayConfig(cold_tier_disk_capacity=1 << 20)


def test_replay_config_disk_requires_dir():
    with pytest.raises(ValueError, match="cold_tier_disk_dir"):
        ReplayConfig(cold_tier_capacity=1 << 16,
                     cold_tier_disk_capacity=1 << 20)


def test_replay_config_disk_knob_bounds():
    kw = dict(cold_tier_capacity=1 << 16,
              cold_tier_disk_capacity=1 << 20,
              cold_tier_disk_dir="/tmp/x")
    assert ReplayConfig(**kw).cold_tier_disk_queue == 16
    with pytest.raises(ValueError, match="cold_tier_disk_queue"):
        ReplayConfig(**kw, cold_tier_disk_queue=0)
    with pytest.raises(ValueError, match="cold_tier_disk_file_bytes"):
        ReplayConfig(**kw, cold_tier_disk_file_bytes=100)
    with pytest.raises(ValueError, match="cold_tier_disk_compact_frac"):
        ReplayConfig(**kw, cold_tier_disk_compact_frac=1.5)
    with pytest.raises(ValueError, match="cold_tier_disk_promote"):
        ReplayConfig(**kw, cold_tier_disk_promote=-1)


def test_replay_config_disk_defaults_off():
    cfg = ReplayConfig()
    assert cfg.cold_tier_disk_capacity == 0
    assert dataclasses.replace(cfg).cold_tier_disk_capacity == 0
