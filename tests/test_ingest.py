"""Ingest staging (runtime/ingest.py + driver integration):

- partial-tail drop accounting at _flush_stage(force=True) in all three
  denominations (flat units, frame-ring live transitions, r2d2 sequence
  upper bound), with blocks shipped one at a time (ingest_coalesce=1,
  the `add` graph) and coalesced (ingest_coalesce=4, `add_many`)
- bitwise ingest parity: the same recorded wire stream lands the
  replay-bound blocks a plain numpy reference in this file computes
  (decode_batch, concatenate, cut into blocks, drop the tail), for
  flat + frame-ring + r2d2 — and the delta-deflate wire codec must land
  the same bits as raw (split decodes exercise the delta continuation
  cache)
- IngestStager unit behavior: boundary splitting, coalesced ships,
  drain compaction, tail exposure
"""

import dataclasses

import jax
import numpy as np
import pytest

from ape_x_dqn_tpu.comm.socket_transport import (
    WireBatch, decode_batch, encode_batch)
from ape_x_dqn_tpu.configs import (
    ActorConfig, EnvConfig, InferenceConfig, LearnerConfig, NetworkConfig,
    ParallelConfig, ReplayConfig, RunConfig, get_config)
from ape_x_dqn_tpu.runtime.driver import ApexDriver
from ape_x_dqn_tpu.runtime.ingest import IngestStager


def _flat_cfg(**replay_kw):
    return get_config("cartpole_smoke").replace(
        replay=ReplayConfig(kind="prioritized", capacity=2048, min_fill=64,
                            **replay_kw),
        learner=LearnerConfig(batch_size=32, n_step=3,
                              target_sync_every=100, publish_every=20),
        actors=ActorConfig(num_actors=1, base_eps=0.5, ingest_batch=16),
        inference=InferenceConfig(max_batch=4, deadline_ms=0.5),
        eval_every_steps=0, eval_episodes=0,
    )


def _ring_cfg(**replay_kw):
    return RunConfig(
        name="catch",
        env=EnvConfig(id="catch", kind="synthetic_atari", frame_skip=4,
                      max_noop_start=4),
        network=NetworkConfig(kind="nature_cnn", dueling=True),
        replay=ReplayConfig(kind="prioritized", capacity=4096, min_fill=128,
                            storage="frame_ring", seg_transitions=8,
                            segs_per_add=2, **replay_kw),
        learner=LearnerConfig(batch_size=32, n_step=3,
                              target_sync_every=100, publish_every=20),
        actors=ActorConfig(num_actors=1, base_eps=0.5, ingest_batch=8),
        inference=InferenceConfig(max_batch=4, deadline_ms=0.5),
        eval_every_steps=0, eval_episodes=0,
    )


def _r2d2_cfg(**replay_kw):
    return get_config("r2d2").replace(
        env=EnvConfig(id="CartPolePO", kind="cartpole_po"),
        network=NetworkConfig(kind="lstm_q", lstm_size=32, torso_dense=64,
                              dueling=True, compute_dtype="float32"),
        replay=ReplayConfig(kind="sequence", capacity=512, seq_length=16,
                            seq_overlap=8, burn_in=4, min_fill=32,
                            priority_eta=0.9, **replay_kw),
        learner=LearnerConfig(batch_size=16, n_step=3, value_rescale=True,
                              target_sync_every=100, lr=1e-3,
                              publish_every=25, train_chunk=4),
        actors=ActorConfig(num_actors=1, base_eps=0.4, ingest_batch=64),
        inference=InferenceConfig(max_batch=8, deadline_ms=1.0),
        parallel=ParallelConfig(dp=1, tp=1),
        eval_every_steps=0, eval_episodes=0,
    )


def _synth_batch(driver, n, seed=0, frames=None):
    """Item-spec-conforming random batch of n staging units."""
    rng = np.random.default_rng(seed)
    batch = {}
    for k, s in driver._item_spec.items():
        shape = (n,) + tuple(s.shape)
        if np.issubdtype(np.dtype(s.dtype), np.integer):
            batch[k] = rng.integers(0, 3, size=shape).astype(s.dtype)
        else:
            batch[k] = (rng.random(shape) * 4).astype(s.dtype)
    ptail = (driver.cfg.replay.seg_transitions,) if driver._frame_mode \
        else ()
    batch["priorities"] = rng.random((n,) + ptail).astype(np.float32)
    if frames is not None:
        batch["frames"] = frames
    return batch


# -- drop accounting, in both ship regimes ---------------------------------
# ingest_coalesce=1: every full buffer is one block and ships through
# the single-block `add` graph (what every run does below min_fill);
# ingest_coalesce=4: a full buffer ships as one `add_many` of 4 blocks.
# One message of coalesce * block + tail units fills exactly one buffer
# and leaves the tail staged; the accounting must close in both.

COALESCE = pytest.mark.parametrize("coalesce", [1, 4])


def _full_buffer_plus(d, tail, coalesce, **batch_kw):
    """Build one message of coalesce full blocks + `tail` units; returns
    (the batch, units in the buffer it will fill and ship)."""
    assert d._stager.coalesce == coalesce
    shipped = coalesce * d.dp * d._stage_chunk
    batch = _synth_batch(d, shipped + tail, **batch_kw)
    return batch, shipped


@COALESCE
def test_flat_tail_drop_accounting(coalesce):
    """Flat denomination: 1 unit = 1 env frame; the dropped tail comes
    OFF _frames_total so frames reconcile with replay contents."""
    d = ApexDriver(_flat_cfg(ingest_coalesce=coalesce))
    tail = 3
    batch, shipped = _full_buffer_plus(d, tail, coalesce)
    d._ingest_one(batch, shipped + tail)
    d._flush_stage(force=True)
    assert d._stage_dropped == tail
    assert d._frames_total == shipped  # ingested minus dropped tail
    assert d._replay_filled == shipped * d._unit_items


@COALESCE
def test_frame_ring_tail_drop_accounting(coalesce):
    """Frame-ring denomination: dropped segments count their LIVE
    transitions (next_off > 0); _frames_total stays (env frames ride
    ingest messages separately in frame mode)."""
    d = ApexDriver(_ring_cfg(ingest_coalesce=coalesce))
    tail = 1
    batch, shipped = _full_buffer_plus(d, tail, coalesce, frames=37)
    # make the tail segment's liveness pattern explicit
    batch["next_off"][shipped:] = 0
    batch["next_off"][shipped:, :5] = 2  # 5 live transitions in the tail
    d._ingest_one(batch, shipped + tail)
    d._flush_stage(force=True)
    assert d._stage_dropped == 5
    assert d._frames_total == 37  # untouched by the drop
    assert d._replay_filled == shipped * d._unit_items


@COALESCE
def test_r2d2_tail_drop_accounting(coalesce):
    """R2D2 denomination: units are sequences; drops count seq_length
    transitions per sequence (upper bound); _frames_total stays."""
    d = ApexDriver(_r2d2_cfg(ingest_coalesce=coalesce))
    tail = 2
    batch, shipped = _full_buffer_plus(d, tail, coalesce, frames=29)
    d._ingest_one(batch, shipped + tail)
    d._flush_stage(force=True)
    assert d._stage_dropped == tail * d.cfg.replay.seq_length
    assert d._frames_total == 29
    assert d._replay_filled == shipped * d._unit_items


# -- per-shard drop closure under the [dp, chunk] round-robin split --------
# (ISSUE 9 satellite 3): the same three denominations, attributed to
# the shard each tail unit WOULD have landed on (unit i -> shard
# i // stage_chunk), with sum(per_shard) == dropped exactly.


def _dp2(cfg):
    return cfg.replace(parallel=ParallelConfig(dp=2, tp=1))


@COALESCE
def test_flat_per_shard_drop_closure_dp2(coalesce):
    d = ApexDriver(_dp2(_flat_cfg(ingest_coalesce=coalesce)))
    assert d.is_dist and d.dp == 2
    chunk = d._stage_chunk
    tail = chunk + 2  # spans shard 0 fully + 2 units into shard 1
    assert d.dp * chunk > tail  # a tail is always shorter than one block
    batch, shipped = _full_buffer_plus(d, tail, coalesce)
    d._ingest_one(batch, shipped + tail)
    d._flush_stage(force=True)
    assert d._stage_dropped == tail
    assert d._stage_dropped_per_shard.tolist() == [chunk, 2]
    assert int(d._stage_dropped_per_shard.sum()) == d._stage_dropped
    assert d._frames_total == shipped
    assert d._replay_filled == shipped * d._unit_items


@COALESCE
def test_frame_ring_per_shard_drop_closure_dp2(coalesce):
    """Frame-ring denomination per shard: each dropped tail segment
    contributes its LIVE transition count to the shard it was bound
    for."""
    d = ApexDriver(_dp2(_ring_cfg(ingest_coalesce=coalesce)))
    assert d.is_dist and d._frame_mode
    chunk = d._stage_chunk
    tail = chunk + 1
    assert d.dp * chunk > tail
    batch, shipped = _full_buffer_plus(d, tail, coalesce, frames=11)
    # tail unit j carries exactly j+1 live transitions
    batch["next_off"][shipped:] = 0
    for j in range(tail):
        batch["next_off"][shipped + j, :j + 1] = 2
    d._ingest_one(batch, shipped + tail)
    d._flush_stage(force=True)
    assert d._stage_dropped == sum(j + 1 for j in range(tail))
    assert d._stage_dropped_per_shard.tolist() == [
        sum(j + 1 for j in range(chunk)), chunk + 1]
    assert int(d._stage_dropped_per_shard.sum()) == d._stage_dropped
    assert d._frames_total == 11  # untouched by frame-mode drops
    assert d._replay_filled == shipped * d._unit_items


@COALESCE
def test_r2d2_per_shard_drop_closure_dp2(coalesce):
    d = ApexDriver(_dp2(_r2d2_cfg(ingest_coalesce=coalesce)))
    assert d.is_dist and d.family == "r2d2"
    chunk = d._stage_chunk
    tail = chunk + 1
    assert d.dp * chunk > tail
    batch, shipped = _full_buffer_plus(d, tail, coalesce, frames=29)
    d._ingest_one(batch, shipped + tail)
    d._flush_stage(force=True)
    seq = d.cfg.replay.seq_length
    assert d._stage_dropped == tail * seq
    assert d._stage_dropped_per_shard.tolist() == [chunk * seq, seq]
    assert int(d._stage_dropped_per_shard.sum()) == d._stage_dropped
    assert d._frames_total == 29
    assert d._replay_filled == shipped * d._unit_items


def test_stager_tail_shard_units_round_robin():
    """IngestStager.tail_shard_units mirrors the [block] -> [dp, chunk]
    C-order reshape: tail unit i belongs to shard i // chunk."""
    st, _ = _unit_stager(block=8, coalesce=2)
    st.put(_rows(8 + 5, 0))
    assert st.drain() == 1  # ships the complete block, compacts 5
    assert st.tail_units() == 5
    assert st.tail_shard_units(2) == [4, 1]  # chunk = 4
    assert st.tail_shard_units(4) == [2, 2, 1, 0]  # chunk = 2
    assert st.tail_shard_units(1) == [5]
    st.discard_tail()
    assert st.tail_shard_units(2) == [0, 0]


def test_drop_accounting_in_run_report():
    """_stage_dropped reaches the run report's ingest_dropped."""
    d = ApexDriver(_flat_cfg())
    block = d.dp * d._stage_chunk
    d._ingest_one(_synth_batch(d, block + 2), block + 2)
    d._flush_stage(force=True)
    assert d._stage_dropped == 2


# -- tiered cold-store denomination (ISSUE 11 satellite 2) -----------------
# With the tier on and the ring full, every ship becomes an eviction
# swap; the pinned closure is evicted == cold_stored + cold_dropped
# (transitions, door outcomes), and recall refills ride the SAME
# staging accounting as fresh ingest (ingest_rows / _replay_filled).


def _cold_ring_cfg(**replay_kw):
    cfg = _ring_cfg()
    kw = dict(capacity=128, min_fill=32, cold_tier_capacity=1024)
    kw.update(replay_kw)
    return cfg.replace(replay=dataclasses.replace(cfg.replay, **kw))


def _fill_ring(d, seed0=0):
    block = d.dp * d._stage_chunk
    for i in range(d.capacity // d._unit_items // block):
        d._ingest_one(_synth_batch(d, block, seed=seed0 + i), block)
    d._stager.drain()
    assert d._replay_filled == d.capacity
    return block


def test_cold_tier_eviction_closure():
    d = ApexDriver(_cold_ring_cfg())
    assert d._cold is not None
    block = _fill_ring(d)
    assert d._cold_evicted == 0  # filling evicts nothing
    for i in range(4):
        d._ingest_one(_synth_batch(d, block, seed=50 + i), block)
    d._stager.drain()
    assert d._cold_evicted > 0
    assert d._cold_evicted == d._cold_stored + d._cold_dropped
    assert d._cold.transitions <= d.cfg.replay.cold_tier_capacity
    # evictions swap slots 1:1 — the hot ring stays exactly full
    assert d._replay_filled == d.capacity


def test_cold_tier_recall_rides_staging_accounting():
    d = ApexDriver(_cold_ring_cfg())
    block = _fill_ring(d)
    for i in range(4):
        d._ingest_one(_synth_batch(d, block, seed=80 + i), block)
    d._stager.drain()
    stored_segs = len(d._cold)
    assert stored_segs > 0
    before = (d._cold_evicted, d._cold_stored + d._cold_dropped)
    assert before[0] == before[1]
    d._cold_refill_tick()   # the ingest loop's idle hook
    d._stager.drain()
    assert d._cold_recalled > 0
    # a recalled block restages through the eviction swap (ring still
    # full), so the closure keeps holding through the churn
    assert d._cold_evicted == d._cold_stored + d._cold_dropped
    assert d._cold_evicted > before[0]
    assert d._replay_filled == d.capacity


def test_cold_off_never_routes_to_eviction_ship():
    """Default path untouched: with the tier off, a full ring keeps
    shipping through the plain add path (blind FIFO)."""
    d = ApexDriver(_cold_ring_cfg(cold_tier_capacity=0))
    assert d._cold is None

    def boom(views, g):  # pragma: no cover - the assertion is the point
        raise AssertionError("cold ship path used with the tier off")

    d._ship_staged_cold = boom
    block = _fill_ring(d)
    for i in range(2):
        d._ingest_one(_synth_batch(d, block, seed=50 + i), block)
    d._stager.drain()
    assert d._replay_filled == d.capacity
    # an idle tick with no cold store is a no-op, not an error
    d._cold_refill_tick()


# -- bitwise ingest parity: the stager vs a plain reference ----------------


def _record_stream(cfg_fn, sizes, payloads):
    """Feed the recorded wire payloads through one driver built from
    cfg_fn, with device shipping stubbed to capture host blocks;
    returns (per-key concatenated rows, dropped, frames_total)."""
    d = ApexDriver(cfg_fn())
    recorded = []

    def ship(views, g):
        recorded.append({k: np.array(v) for k, v in views.items()})
        return []

    d._stager._ship = ship
    for n, payload in zip(sizes, payloads):
        d._ingest_one(WireBatch(payload), n)
    d._flush_stage(force=True)
    keys = tuple(d._item_spec) + ("priorities",)
    rows = {k: (np.concatenate([r[k] for r in recorded])
                if recorded else None) for k in keys}
    return rows, d._stage_dropped, d._frames_total


def _reference_stream(cfg_fn, payloads):
    """What the recorded stream must come to, in plain numpy and with
    no staging code: decode every payload, concatenate in arrival
    order, keep the whole dp * stage_chunk blocks, drop the tail and
    count it in the configuration's denomination. Only the block
    geometry and the family are read off a driver."""
    probe = ApexDriver(cfg_fn())
    block = probe.dp * probe._stage_chunk
    keys = tuple(probe._item_spec) + ("priorities",)
    batches = [decode_batch(p) for p in payloads]
    rows = {k: np.concatenate([np.asarray(b[k]) for b in batches])
            for k in keys}
    total = rows["priorities"].shape[0]
    kept = total // block * block
    frames = sum(int(b["frames"]) for b in batches)
    if probe._frame_mode:
        dropped = int((rows["next_off"][kept:] > 0).sum())
    elif probe.cfg.replay.kind == "sequence":
        dropped = (total - kept) * probe.cfg.replay.seq_length
    else:
        dropped = total - kept
        frames -= dropped
    return ({k: (v[:kept] if kept else None) for k, v in rows.items()},
            dropped, frames)


def _assert_same_stream(got, want):
    assert got[1] == want[1]  # dropped
    assert got[2] == want[2]  # frames_total
    for k in want[0]:
        a, b = got[0][k], want[0][k]
        assert (a is None) == (b is None), k
        if a is not None:
            assert a.dtype == b.dtype, k
            np.testing.assert_array_equal(a, b, err_msg=k)


def _recorded_payloads(cfg_fn, codecs):
    """Ragged batch sizes, so staging boundaries are crossed
    mid-batch; one payload list per codec, of the same batches."""
    probe = ApexDriver(cfg_fn())
    sizes = [3, 7, 1, 6, 5, 2]
    batches = [_synth_batch(probe, n, seed=100 + i, frames=n)
               for i, n in enumerate(sizes)]
    return sizes, [[encode_batch(b, c) for b in batches] for c in codecs]


@pytest.mark.parametrize("cfg_fn", [_flat_cfg, _ring_cfg, _r2d2_cfg],
                         ids=["flat", "frame_ring", "r2d2"])
def test_ingest_parity_stager_vs_reference(cfg_fn):
    """The recorded wire stream must land, through the driver's
    stager, bitwise the replay-bound blocks the plain reference cuts
    from it, with the same drop accounting."""
    sizes, (payloads,) = _recorded_payloads(cfg_fn, ["raw"])
    _assert_same_stream(_record_stream(cfg_fn, sizes, payloads),
                        _reference_stream(cfg_fn, payloads))


@pytest.mark.parametrize("cfg_fn", [_flat_cfg, _ring_cfg, _r2d2_cfg],
                         ids=["flat", "frame_ring", "r2d2"])
def test_ingest_parity_codec_vs_raw(cfg_fn):
    """The delta-deflate wire codec must be invisible to replay: the
    SAME recorded stream encoded raw vs codec lands bitwise-identical
    blocks through the staging path (split decodes, delta continuation
    across buffer boundaries and all), and both are what the plain
    reference makes of the codec stream, in every denomination."""
    sizes, (raw_payloads, codec_payloads) = _recorded_payloads(
        cfg_fn, ["raw", "delta-deflate"])
    raw = _record_stream(cfg_fn, sizes, raw_payloads)
    _assert_same_stream(_record_stream(cfg_fn, sizes, codec_payloads),
                        raw)
    _assert_same_stream(raw, _reference_stream(cfg_fn, codec_payloads))


# -- IngestStager unit behavior --------------------------------------------


def _unit_stager(block=4, coalesce=2, buffers=2):
    spec = {"x": jax.ShapeDtypeStruct((2,), np.float32),
            "y": jax.ShapeDtypeStruct((), np.int32)}
    shipped = []

    def ship(views, g):
        shipped.append((g, {k: np.array(v) for k, v in views.items()}))
        return []

    return IngestStager(spec, (), block, coalesce, buffers, ship), shipped


def _rows(n, base):
    return {"x": np.arange(n * 2, dtype=np.float32).reshape(n, 2) + base,
            "y": np.arange(n, dtype=np.int32) + base,
            "priorities": np.arange(n, dtype=np.float32) + base}


def test_stager_coalesced_ship_and_boundary_split():
    st, shipped = _unit_stager(block=4, coalesce=2)
    st.put(_rows(3, 0))          # cursor 3
    st.put(_rows(7, 100))        # fills 8 (ship g=2) + 2 into next buffer
    assert len(shipped) == 1
    g, views = shipped[0]
    assert g == 2 and views["x"].shape == (8, 2)
    # the 8 shipped rows are the stream's first 8, in order
    expect = np.concatenate([_rows(3, 0)["x"], _rows(7, 100)["x"][:5]])
    np.testing.assert_array_equal(views["x"], expect)
    assert st.tail_units() == 2
    assert st.occupancy() == pytest.approx(2 / 8)


def test_stager_drain_ships_blocks_and_compacts():
    st, shipped = _unit_stager(block=4, coalesce=2)
    st.put(_rows(6, 0))          # cursor 6: one full block + 2 rem
    assert st.drain() == 1
    assert len(shipped) == 1 and shipped[0][0] == 1
    np.testing.assert_array_equal(shipped[0][1]["x"], _rows(6, 0)["x"][:4])
    # remainder compacted to the buffer front
    assert st.tail_units() == 2
    np.testing.assert_array_equal(st.tail_view("x"), _rows(6, 0)["x"][4:])
    # draining again with no complete block is a no-op
    assert st.drain() == 0
    # the compacted rows still flow into the next coalesced group
    st.put(_rows(6, 50))
    assert len(shipped) == 2 and shipped[1][0] == 2
    expect = np.concatenate([_rows(6, 0)["x"][4:], _rows(6, 50)["x"]])
    np.testing.assert_array_equal(shipped[1][1]["x"], expect)
    assert st.tail_units() == 0


def test_stager_wire_batch_decode_into():
    """WireBatch payloads land via decode_into (the zero-copy path) and
    match what the dict path stages bitwise."""
    st_wire, shipped_wire = _unit_stager(block=4, coalesce=1)
    st_dict, shipped_dict = _unit_stager(block=4, coalesce=1)
    for i, n in enumerate([3, 5, 4]):
        rows = _rows(n, 10 * i)
        st_wire.put(WireBatch(encode_batch(rows)))
        st_dict.put(rows)
    assert len(shipped_wire) == len(shipped_dict) == 3
    for (gw, vw), (gd, vd) in zip(shipped_wire, shipped_dict):
        assert gw == gd
        for k in vw:
            np.testing.assert_array_equal(vw[k], vd[k], err_msg=k)


def test_stager_discard_tail():
    st, shipped = _unit_stager(block=4, coalesce=2)
    st.put(_rows(3, 0))
    assert st.tail_units() == 3
    st.discard_tail()
    assert st.tail_units() == 0 and shipped == []


# -- per-shard cold-door closure + the disk rung (PR 16) -------------------
# The dist eviction swap runs per dp shard, so the closure holds PER
# SHARD: evicted[d] == stored[d] + dropped[d], sums matching the
# scalar counters exactly. The disk rung hangs off the RAM door and
# never perturbs that closure (spills/promotions are side traffic).


def test_cold_tier_dp2_per_shard_closure():
    d = ApexDriver(_dp2(_cold_ring_cfg()))
    assert d.is_dist and d.dp == 2 and d._cold is not None
    block = _fill_ring(d)
    for i in range(4):
        d._ingest_one(_synth_batch(d, block, seed=60 + i), block)
    d._stager.drain()
    assert d._cold_evicted > 0
    per_ev = d._cold_evicted_per_shard
    assert per_ev.shape == (2,) and (per_ev > 0).all()
    np.testing.assert_array_equal(
        per_ev, d._cold_stored_per_shard + d._cold_dropped_per_shard)
    assert int(per_ev.sum()) == d._cold_evicted
    assert int(d._cold_stored_per_shard.sum()) == d._cold_stored
    assert int(d._cold_dropped_per_shard.sum()) == d._cold_dropped
    assert d._cold_evicted == d._cold_stored + d._cold_dropped
    assert d._replay_filled == d.capacity
    # per-shard ring sizes stay full through the swap churn
    sizes = np.asarray(d.state.replay.size)
    assert sizes.shape == (2,)
    assert (sizes == d.capacity // d.dp).all()


def test_cold_tier_dp2_recall_keeps_per_shard_closure():
    d = ApexDriver(_dp2(_cold_ring_cfg()))
    block = _fill_ring(d)
    for i in range(4):
        d._ingest_one(_synth_batch(d, block, seed=70 + i), block)
    d._stager.drain()
    assert len(d._cold) > 0
    d._cold_refill_tick()
    d._stager.drain()
    assert d._cold_recalled > 0
    np.testing.assert_array_equal(
        d._cold_evicted_per_shard,
        d._cold_stored_per_shard + d._cold_dropped_per_shard)
    assert int(d._cold_evicted_per_shard.sum()) == d._cold_evicted


def _disk_cfg(tmp_path, **replay_kw):
    kw = dict(cold_tier_capacity=32,  # ~3 eviction blocks' worth of
              # live transitions: later puts displace or drop -> spills
              cold_tier_disk_capacity=1 << 16,
              cold_tier_disk_dir=str(tmp_path / "spill"))
    kw.update(replay_kw)
    return _cold_ring_cfg(**kw)


def test_cold_disk_captures_door_losers(tmp_path):
    d = ApexDriver(_disk_cfg(tmp_path))
    assert d._disk is not None
    block = _fill_ring(d)
    for i in range(8):
        d._ingest_one(_synth_batch(d, block, seed=90 + i), block)
    d._stager.drain()
    d._disk.drain(timeout=10.0)
    s = d._disk.stats()
    assert d._cold.spilled > 0
    assert s["spilled"] == d._cold.spilled  # queue never refused here
    assert s["transitions"] > 0 and s["io_errors"] == 0
    # the eviction closure is untouched by spill traffic
    assert d._cold_evicted == d._cold_stored + d._cold_dropped
    assert d._cold.transitions <= d.cfg.replay.cold_tier_capacity
    d._disk.close()


def test_cold_disk_refill_tick_promotes(tmp_path):
    d = ApexDriver(_disk_cfg(tmp_path))
    block = _fill_ring(d)
    for i in range(8):
        d._ingest_one(_synth_batch(d, block, seed=110 + i), block)
    d._stager.drain()
    d._disk.drain(timeout=10.0)
    assert d._disk.stats()["segments"] > 0
    # the idle tick recalls RAM segments first (making door room), then
    # promotes the heaviest disk segment back through put_segment
    d._cold_refill_tick()
    d._stager.drain()
    assert d._disk.stats()["promoted"] >= 1
    assert d._cold_evicted == d._cold_stored + d._cold_dropped
    d._disk.close()


def test_cold_disk_dp2_per_shard_closure(tmp_path):
    d = ApexDriver(_dp2(_disk_cfg(tmp_path)))
    assert d.is_dist and d._disk is not None
    block = _fill_ring(d)
    for i in range(6):
        d._ingest_one(_synth_batch(d, block, seed=130 + i), block)
    d._stager.drain()
    d._disk.drain(timeout=10.0)
    assert d._cold.spilled > 0
    np.testing.assert_array_equal(
        d._cold_evicted_per_shard,
        d._cold_stored_per_shard + d._cold_dropped_per_shard)
    assert int(d._cold_evicted_per_shard.sum()) == d._cold_evicted
    d._disk.close()


def test_cold_disk_stats_reach_run_report_shape(tmp_path):
    """The disk block in the driver's run() output mirrors
    DiskStore.stats() — pin the keys obs and the tests read."""
    d = ApexDriver(_disk_cfg(tmp_path))
    s = d._disk.stats()
    assert set(s) >= {"segments", "transitions", "bytes", "files",
                      "spilled", "promoted", "dropped", "queue_full",
                      "io_errors", "corrupt_segments", "compactions"}
    d._disk.close()
