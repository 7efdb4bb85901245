"""Cross-host transport: native framing codec, TCP ingest/param paths,
remote actor hosts, and actor-loss fault injection (SURVEY.md §2.3 item
3 "gRPC -> DCN ingest", §5 "failure detection")."""

import dataclasses
import os
import signal
import subprocess
import sys
import threading
import time
import zlib

import numpy as np
import pytest

from ape_x_dqn_tpu.comm import native
from ape_x_dqn_tpu.comm.socket_transport import (
    SocketIngestServer, SocketTransport, decode_batch, encode_batch)
from ape_x_dqn_tpu.configs import (
    ActorConfig, InferenceConfig, LearnerConfig, ReplayConfig, get_config)
from ape_x_dqn_tpu.runtime.driver import ApexDriver


# -- native codec ------------------------------------------------------------


def test_native_codec_compiles_and_loads():
    """g++ is in this image: the C++ data plane must actually build."""
    assert native.have_native()


def test_native_crc32_matches_zlib():
    data = os.urandom(4096)
    assert native.crc32(data) == zlib.crc32(data) & 0xFFFFFFFF
    assert native.crc32(b"") == 0
    # seeded/rolling form matches too
    a, b = data[:100], data[100:]
    assert native.crc32(b, native.crc32(a)) == zlib.crc32(data) & 0xFFFFFFFF


def test_pack_unpack_roundtrip():
    chunks = [b"", b"x", os.urandom(1000), b"tail"]
    frame = native.pack_records(chunks)
    assert native.unpack_records(frame) == chunks
    with pytest.raises(ValueError):
        native.unpack_records(frame[:-1])  # truncated record


def test_batch_codec_roundtrip():
    batch = {
        "obs": np.random.randint(0, 255, (7, 84, 84, 4), dtype=np.uint8),
        "action": np.arange(7, dtype=np.int32),
        "priorities": np.random.rand(7).astype(np.float32),
        "actor": 3,
        "frames": 42,
    }
    out = decode_batch(encode_batch(batch))
    assert out["actor"] == 3 and out["frames"] == 42
    for k in ("obs", "action", "priorities"):
        np.testing.assert_array_equal(out[k], batch[k])
        assert out[k].dtype == batch[k].dtype


def test_unpack_records_mv_zero_copy():
    """The memoryview unpack path returns views ALIASING the frame (no
    copies) with contents identical to the copying path."""
    chunks = [b"", b"x", os.urandom(1000), b"tail"]
    frame = native.pack_records(chunks)
    mvs = native.unpack_records_mv(frame)
    assert [bytes(m) for m in mvs] == chunks
    for m in mvs:
        assert isinstance(m, memoryview)
        assert m.obj is frame  # view into the frame itself, not a copy
    # bytearray frames (what _recv_exact returns) work identically
    mvs2 = native.unpack_records_mv(bytearray(frame))
    assert [bytes(m) for m in mvs2] == chunks
    with pytest.raises(ValueError):
        native.unpack_records_mv(frame[:-1])


def test_decode_batch_into_matches_decode_batch():
    """Decode-into-staging lands bitwise what decode_batch returns, for
    the whole batch and for arbitrary [start, start+limit) windows at
    arbitrary staging offsets."""
    from ape_x_dqn_tpu.comm.socket_transport import decode_batch_into
    batch = {
        "obs": np.random.randint(0, 255, (7, 8, 8, 2), dtype=np.uint8),
        "action": np.arange(7, dtype=np.int32),
        "priorities": np.random.rand(7).astype(np.float32),
        "actor": 3, "frames": 42,
    }
    payload = encode_batch(batch)
    ref = decode_batch(payload)

    def fresh(cap):
        return {k: np.zeros((cap,) + v.shape[1:], v.dtype)
                for k, v in ref.items() if isinstance(v, np.ndarray)}

    dest = fresh(7)
    k, rows, scalars = decode_batch_into(payload, dest, 0)
    assert (k, rows) == (7, 7)
    assert scalars == {"actor": 3, "frames": 42}
    for key, v in dest.items():
        np.testing.assert_array_equal(v, ref[key], err_msg=key)
    # partial window [2, 5) landing at offset 4
    dest = fresh(16)
    k, rows, _ = decode_batch_into(payload, dest, 4, start=2, limit=3)
    assert (k, rows) == (3, 7)
    for key, v in dest.items():
        np.testing.assert_array_equal(v[4:7], ref[key][2:5], err_msg=key)
        assert not v[:4].any() and not v[7:].any(), key
    # limit past the end clamps
    dest = fresh(16)
    k, _, _ = decode_batch_into(payload, dest, 0, start=5, limit=99)
    assert k == 2


def test_wire_batch_dict_protocol():
    """WireBatch serves every consumer that treated the queue payload as
    a decoded dict: item access, .get defaults, scalars, row count."""
    from ape_x_dqn_tpu.comm.socket_transport import WireBatch, batch_rows
    batch = {
        "obs": np.random.rand(5, 3).astype(np.float32),
        "priorities": np.random.rand(5).astype(np.float32),
        "actor": 1, "frames": 9,
    }
    wb = WireBatch(encode_batch(batch))
    assert wb.rows == 5 and batch_rows(wb) == 5
    assert batch_rows(batch) == 5  # dict form reads priorities
    assert wb.get("frames", 5) == 9 and wb.get("missing") is None
    assert wb["actor"] == 1
    np.testing.assert_array_equal(wb["obs"], batch["obs"])
    assert wb["obs"] is wb["obs"]  # materialized arrays are cached
    assert "priorities" in wb and "nope" not in wb
    assert set(wb.keys()) == set(batch.keys())
    with pytest.raises(KeyError):
        wb["nope"]


def test_server_get_params_caches_deserialized_tree():
    """The learner-host local param pull must not pay a pickle
    round-trip per call: the deserialized tree is cached per version
    and invalidated on the next publish."""
    server = SocketIngestServer("127.0.0.1", 0)
    try:
        server.publish_params({"w": np.ones(3, np.float32)}, 5)
        p1, v1 = server.get_params()
        p2, v2 = server.get_params()
        assert v1 == v2 == 5
        assert p1["w"] is p2["w"]  # cached, not re-deserialized
        server.publish_params({"w": np.full(3, 2.0, np.float32)}, 6)
        p3, v3 = server.get_params()
        assert v3 == 6
        np.testing.assert_array_equal(p3["w"], np.full(3, 2.0))
    finally:
        server.stop()


def test_server_stop_drains_parked_batches():
    """Regression (apexlint v3 resource-lifecycle sweep): stop() must
    drain the bounded ingest queue and release() whatever is parked in
    it — a batch stranded there at shutdown pins its resources (for an
    shm slot batch, the ring slot AND the mapping; the PR 18 bug
    class in queue form)."""
    class Releasable(dict):
        released = 0

        def release(self):
            type(self).released += 1

    server = SocketIngestServer("127.0.0.1", 0)
    try:
        for i in range(3):
            server.send_experience(Releasable(actor=i))
        assert server._q.qsize() == 3
    finally:
        server.stop()
    assert server._q.qsize() == 0
    assert Releasable.released == 3


def test_loopback_close_drains_queue():
    """Regression (same sweep): LoopbackTransport gained close() so
    batches parked in the bounded queue are not pinned by a transport
    nobody will read again; drivers call close() symmetrically."""
    from ape_x_dqn_tpu.comm.transport import LoopbackTransport

    t = LoopbackTransport(max_pending=4)
    for i in range(3):
        t.send_experience({"actor": i})
    assert t.pending == 3
    t.close()
    assert t.pending == 0
    t.close()  # idempotent on an empty queue


# -- wire codec (delta-deflate experience compression) ----------------------


def _codec_batch(seed=0, n=16):
    """Frame-heavy batch with every leaf class the codec handles:
    frame-like uint8 (xd), bools (bp), small ints (d), floats (raw)."""
    rng = np.random.default_rng(seed)
    base = rng.integers(0, 60, (4, 84, 84), dtype=np.uint8)
    frames = np.stack([np.roll(base, i, axis=1) for i in range(n)])
    return {
        "seg_frames": frames,
        "action": rng.integers(0, 18, (n,)).astype(np.int32),
        "done": rng.random(n) < 0.1,
        "priorities": (rng.random(n) + 0.1).astype(np.float32),
        "actor": 1, "frames": n,
    }


def _assert_batches_equal(got, batch):
    for k, v in batch.items():
        if isinstance(v, np.ndarray):
            assert got[k].dtype == v.dtype, k
            np.testing.assert_array_equal(got[k], v, err_msg=k)
        else:
            assert got[k] == v, k


def test_wire_codec_shrinks_and_roundtrips():
    """Frame traffic must compress >=2x (the adoption bar) and decode
    bitwise-identically, through both decode forms."""
    from ape_x_dqn_tpu.comm.socket_transport import (
        WireBatch, decode_batch_into)

    batch = _codec_batch()
    raw = encode_batch(batch, "raw")
    comp = encode_batch(batch, "delta-deflate")
    assert len(comp) * 2 < len(raw)
    _assert_batches_equal(decode_batch(comp), batch)
    wb = WireBatch(comp)
    assert wb.raw_nbytes > wb.wire_nbytes
    dest = {k: np.zeros_like(v) for k, v in batch.items()
            if isinstance(v, np.ndarray)}
    k1, rows, scalars = decode_batch_into(comp, dest, 0, 0, 9)
    wb.decode_into(dest, 9, 9)  # split continuation on a fresh WireBatch
    assert rows == 16 and scalars["actor"] == 1
    for k in dest:
        np.testing.assert_array_equal(dest[k], batch[k], err_msg=k)


def test_decode_leaf_full_copies_are_load_bearing():
    """The .copy()s in _decode_leaf_full are ownership, not
    convenience (ISSUE 18 satellite): a materialized leaf must survive
    its source buffer being scribbled over — a ShmSlotBatch's ring
    slot is REUSED by the writer the moment release() frees it, and a
    zlib-inflated codec leaf lives in a per-payload cache the array
    must outlive — and "xd" leaves need writable memory for the
    in-place XOR undo. Dropping either copy silently corrupts
    delivered batches; this pins them."""
    from ape_x_dqn_tpu.comm.socket_transport import WireBatch

    batch = _codec_batch(seed=11)
    # raw path: decode from a writable buffer (what a ring slot is),
    # then scribble over it as a reusing writer would
    payload = bytearray(encode_batch(batch, "raw"))
    wb = WireBatch(memoryview(payload))
    frames = wb["seg_frames"]
    pris = wb["priorities"]
    want_f, want_p = batch["seg_frames"].copy(), batch["priorities"].copy()
    payload[:] = b"\xaa" * len(payload)  # slot reuse
    np.testing.assert_array_equal(frames, want_f)
    np.testing.assert_array_equal(pris, want_p)
    # ownership, not a view into the (now-scribbled) transport buffer
    assert frames.base is None or frames.flags["OWNDATA"]
    # codec path: "d"/"xd" leaves must come back writable (the xd
    # decode XORs rows in place; a frombuffer view of immutable zlib
    # output would raise) and detached from the decode cache
    comp = encode_batch(batch, "delta-deflate")
    wc = WireBatch(comp)
    arr = wc["seg_frames"]
    np.testing.assert_array_equal(arr, want_f)
    assert arr.flags["WRITEABLE"]
    arr[0, 0, 0, 0] ^= 0xFF  # must not raise, must not poison the cache
    np.testing.assert_array_equal(wc["action"], batch["action"])


def test_wire_codec_interop_matrix():
    """Every (server wire_codec) x (client wire_codec) combination over
    a REAL socket pair delivers bitwise-identical experience, and the
    negotiated codec is delta-deflate iff both sides want it."""
    batch = _codec_batch(seed=3)
    for srv_codec in ("raw", "delta-deflate"):
        for cli_codec in ("raw", "delta-deflate"):
            server = SocketIngestServer("127.0.0.1", 0,
                                        wire_codec=srv_codec)
            client = SocketTransport("127.0.0.1", server.port,
                                     wire_codec=cli_codec)
            try:
                client.send_experience(batch)
                got = server.recv_experience(timeout=5.0)
                assert got is not None, (srv_codec, cli_codec)
                _assert_batches_equal(got, batch)
                want = "delta-deflate" \
                    if srv_codec == cli_codec == "delta-deflate" else "raw"
                assert client.negotiated_codec == want
                if want == "delta-deflate":
                    assert server.wire_compression_ratio > 1.5
                    assert client.wire_compression_ratio > 1.5
            finally:
                client.close()
                server.stop()


def test_wire_codec_raw_fallback_on_silent_server():
    """An OLD server never acks MSG_HELLO (unknown types fall through
    its reader) — the client must time out and degrade to raw, and the
    raw message must still arrive. Simulated with a minimal reader that
    ignores everything but experience messages."""
    import socket as socket_mod

    from ape_x_dqn_tpu.comm.socket_transport import (
        MSG_EXPERIENCE, _recv_msg)

    listener = socket_mod.socket(socket_mod.AF_INET,
                                 socket_mod.SOCK_STREAM)
    listener.bind(("127.0.0.1", 0))
    listener.listen(1)
    got: list = []

    def old_server():
        conn, _ = listener.accept()
        while True:
            msg = _recv_msg(conn)
            if msg is None:
                return
            if msg[0] == MSG_EXPERIENCE:  # hellos silently ignored
                got.append(msg[1])
                return

    thread = threading.Thread(target=old_server, daemon=True)
    thread.start()
    client = SocketTransport("127.0.0.1", listener.getsockname()[1],
                             hello_timeout=0.3)
    try:
        batch = _codec_batch(seed=4)
        client.send_experience(batch)
        assert client.negotiated_codec == "raw"
        thread.join(timeout=5)
        assert got, "old server never received the raw experience"
        _assert_batches_equal(decode_batch(got[0]), batch)
    finally:
        client.close()
        listener.close()


def test_wire_codec_cross_decode_native_python(monkeypatch):
    """The C++ delta transform and the numpy fallback must be
    wire-compatible in BOTH directions: payloads encoded with one must
    decode bitwise through the other (a C++-enabled learner host talks
    to a Python-only actor host and vice versa)."""
    if not native.have_delta_native():
        pytest.skip("native delta unavailable; nothing to cross-check")
    batch = _codec_batch(seed=5)
    native_payload = encode_batch(batch, "delta-deflate")
    native_decode = decode_batch(native_payload)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", True)
    monkeypatch.setattr(native, "_has_delta", False)
    python_payload = encode_batch(batch, "delta-deflate")
    _assert_batches_equal(decode_batch(native_payload), batch)
    monkeypatch.undo()
    assert native.have_delta_native()
    _assert_batches_equal(decode_batch(python_payload), batch)
    _assert_batches_equal(native_decode, batch)


@pytest.mark.parametrize("seed", range(4))
def test_wire_codec_fuzz_roundtrip(seed):
    """Random leaf shapes/dtypes/row sizes round-trip bitwise under the
    codec, through decode_batch AND the staged decode_batch_into with a
    random split point."""
    from ape_x_dqn_tpu.comm.socket_transport import decode_batch_into

    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 40))
    batch = {"priorities": rng.random(n).astype(np.float32)}
    dtypes = [np.uint8, np.int8, np.int32, np.int64, np.float32,
              np.float64, np.bool_]
    for i in range(int(rng.integers(1, 6))):
        nd = int(rng.integers(0, 3))
        tail = tuple(int(rng.integers(1, 64)) for _ in range(nd))
        dt = dtypes[int(rng.integers(0, len(dtypes)))]
        shape = (n,) + tail
        if dt == np.bool_:
            batch[f"leaf{i}"] = rng.random(shape) < 0.2
        elif np.issubdtype(dt, np.integer):
            batch[f"leaf{i}"] = rng.integers(0, 7, shape).astype(dt)
        else:
            batch[f"leaf{i}"] = rng.random(shape).astype(dt)
    payload = encode_batch(batch, "delta-deflate")
    _assert_batches_equal(decode_batch(payload), batch)
    dest = {k: np.zeros_like(v) for k, v in batch.items()}
    cut = int(rng.integers(0, n + 1))
    decode_batch_into(payload, dest, 0, 0, cut)
    decode_batch_into(payload, dest, cut, cut)
    for k in dest:
        np.testing.assert_array_equal(dest[k], batch[k], err_msg=k)


def test_wire_codec_truncated_and_corrupt_rejected():
    """Corrupt/truncated codec streams must reject with ValueError (the
    server reader drops such connections), never decode garbage."""
    import json as json_mod

    from ape_x_dqn_tpu.comm import native as native_mod

    batch = _codec_batch(seed=7)
    payload = encode_batch(batch, "delta-deflate")
    # flip bytes inside the compressed frame region
    corrupt = bytearray(payload)
    corrupt[len(corrupt) // 2] ^= 0xFF
    corrupt[-100] ^= 0xFF
    with pytest.raises(ValueError):
        decode_batch(bytes(corrupt))
    # truncate a leaf's deflate stream but keep the framing valid:
    # re-pack with the last record cut short
    recs = [bytes(r) for r in native_mod.unpack_records_mv(payload)]
    meta = json_mod.loads(recs[0])
    assert any(m.get("enc") for m in meta)  # codec leaves present
    truncated = native_mod.pack_records(recs[:-1] + [recs[-1][:10]])
    with pytest.raises(ValueError):
        decode_batch(truncated)
    # a stream inflating to the WRONG size (valid zlib, bad length):
    # swap one encoded leaf's bytes for a short valid deflate stream
    xd_idx = 1 + [j for j, m in enumerate(
        [m for m in meta if m["nd"]]) if m.get("enc") == "xd"][0]
    recs[xd_idx] = zlib.compress(b"short", 1)
    with pytest.raises(ValueError):
        decode_batch(native_mod.pack_records(recs))


# -- socket transport --------------------------------------------------------


def test_socket_transport_experience_and_params():
    server = SocketIngestServer("127.0.0.1", 0)
    client = SocketTransport("127.0.0.1", server.port)
    try:
        # params flow learner -> actor
        server.publish_params({"w": np.ones(3, np.float32)}, 5)
        params, version = client.get_params()
        assert version == 5
        np.testing.assert_array_equal(params["w"], np.ones(3))

        # experience flows actor -> learner
        batch = {"obs": np.zeros((4, 2), np.float32),
                 "priorities": np.ones(4, np.float32), "actor": 0,
                 "frames": 4}
        client.send_experience(batch)
        got = server.recv_experience(timeout=5.0)
        assert got is not None and got["frames"] == 4
        np.testing.assert_array_equal(got["priorities"], batch["priorities"])
    finally:
        client.close()
        server.stop()


def test_param_wire_dtype_bf16_halves_blob():
    """DCN weight broadcast ships f32 params as bf16 (half the bytes —
    the soak measured param pulls saturating the link) and the
    receiver upcasts back to f32 with only bf16 rounding applied."""
    import ml_dtypes

    rng = np.random.default_rng(0)
    params = {"w": rng.normal(size=(256, 256)).astype(np.float32),
              "b": rng.normal(size=256).astype(np.float32),
              "frames": np.zeros((4, 4), np.uint8)}  # non-float: as-is
    bf = SocketIngestServer("127.0.0.1", 0)  # default bfloat16
    f32 = SocketIngestServer("127.0.0.1", 0,
                             param_wire_dtype="float32")
    try:
        bf.publish_params(params, 3)
        f32.publish_params(params, 3)
        assert len(bf._param_blob()) < 0.6 * len(f32._param_blob())
        got, version = bf.get_params()
        assert version == 3
        assert got["w"].dtype == np.float32  # receiver upcasts
        assert got["frames"].dtype == np.uint8
        # values survive with bf16 rounding only (~2^-8 relative)
        np.testing.assert_allclose(got["w"], params["w"],
                                   rtol=1 / 128, atol=1e-6)
        exact = np.asarray(params["w"]).astype(
            ml_dtypes.bfloat16).astype(np.float32)
        np.testing.assert_array_equal(got["w"], exact)
        # the exact path stays bit-identical
        got32, _ = f32.get_params()
        np.testing.assert_array_equal(got32["w"], params["w"])
    finally:
        bf.stop()
        f32.stop()


def test_native_bf16_leaves_keep_dtype_on_the_wire():
    """Only leaves the SENDER downcast are upcast at the receiver: a
    param tree with genuinely-bf16 leaves (e.g. a bf16-param network)
    must keep them bf16 across the wire under BOTH wire dtypes
    (round-3 advisor finding: the old receiver upcast every bf16 leaf
    unconditionally)."""
    import ml_dtypes

    params = {"w32": np.ones((8, 8), np.float32),
              "wbf": np.full((8, 8), 1.5, ml_dtypes.bfloat16)}
    for wire in ("bfloat16", "float32"):
        srv = SocketIngestServer("127.0.0.1", 0, param_wire_dtype=wire)
        try:
            srv.publish_params(params, 1)
            got, _ = srv.get_params()
            assert got["w32"].dtype == np.float32, wire
            assert got["wbf"].dtype == ml_dtypes.bfloat16, wire
            np.testing.assert_array_equal(
                got["wbf"].astype(np.float32), 1.5)
        finally:
            srv.stop()


def test_conn_tracking_under_connect_disconnect_hammer():
    """_conns is mutated by the accept + reader threads while the
    multihost idle check reads it (round-2 verdict weak #6): hammer
    connect/disconnect cycles against concurrent active_connections /
    quiesced readers and assert the count settles to exactly zero with
    the debounce behaving."""
    import socket as socketlib
    import threading
    import time

    server = SocketIngestServer("127.0.0.1", 0, idle_grace_s=2.0)
    stop = threading.Event()
    snapshots: list[int] = []

    def reader():
        while not stop.is_set():
            n = server.active_connections
            assert n >= 0
            snapshots.append(n)
            server.quiesced()  # must never raise mid-churn

    rthreads = [threading.Thread(target=reader, daemon=True)
                for _ in range(2)]
    for t in rthreads:
        t.start()
    try:
        saw_open = False
        for it in range(30):
            socks = [socketlib.create_connection(("127.0.0.1", server.port),
                                                 timeout=5)
                     for _ in range(4)]
            if not saw_open:
                # observe a live count at least once while socks are open
                # (the accept thread needs a moment on a 1-core host)
                deadline = time.monotonic() + 5
                while (server.active_connections == 0
                       and time.monotonic() < deadline):
                    time.sleep(0.01)
                saw_open = server.active_connections > 0
            for s in socks:
                s.close()
        assert saw_open, "accept loop never registered a connection"
        deadline = time.monotonic() + 5
        while server.active_connections and time.monotonic() < deadline:
            time.sleep(0.05)
        assert server.active_connections == 0
        assert snapshots, "concurrent readers never ran"
        # a disconnect just happened: the idle verdict must debounce
        assert not server.quiesced()
        # ... and eventually clear. Poll rather than a single sleep:
        # sockets closed before being accepted can be accepted LATE by
        # the 0.2s-poll accept loop, refreshing the disconnect stamp
        # after the settle check (seen flaky under full-suite load)
        deadline = time.monotonic() + 20
        while not server.quiesced() and time.monotonic() < deadline:
            time.sleep(0.2)
        assert server.quiesced()
    finally:
        stop.set()
        for t in rthreads:
            t.join(timeout=2)
        server.stop()


def test_socket_client_survives_dead_server():
    """Ingest is lossy-tolerant: a broken connection must not raise into
    the actor loop — batches count as dropped."""
    server = SocketIngestServer("127.0.0.1", 0)
    port = server.port
    client = SocketTransport("127.0.0.1", port)
    batch = {"x": np.ones(2, np.float32), "priorities": np.ones(2),
             "actor": 0}
    client.send_experience(batch)
    assert server.recv_experience(timeout=5.0) is not None
    server.stop()
    time.sleep(0.2)
    # the first sends may land in the kernel buffer before the RST
    # surfaces; keep sending until the client notices and starts dropping
    for _ in range(20):
        client.send_experience(batch)  # must never raise
        if client.dropped:
            break
        time.sleep(0.05)
    assert client.dropped >= 1
    client.close()


def _learner_cfg(num_local_actors=1):
    return get_config("cartpole_smoke").replace(
        actors=ActorConfig(num_actors=num_local_actors, base_eps=0.6,
                           ingest_batch=16),
        replay=ReplayConfig(kind="prioritized", capacity=2048, min_fill=64),
        # steps_per_frame_cap: this host shares ONE core with the remote
        # actor process; a free-running learner starves the ingest thread
        # and the bounded queue drops most of the experience stream
        learner=LearnerConfig(batch_size=32, n_step=3,
                              target_sync_every=100, publish_every=20,
                              steps_per_frame_cap=1.0),
        inference=InferenceConfig(max_batch=8, deadline_ms=1.0),
        eval_every_steps=0, eval_episodes=0,
    )


def _spawn_actor_host(port: int, frames: int, offset: int = 1):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.Popen(
        [sys.executable, "-m", "ape_x_dqn_tpu.runtime.actor_host",
         "--config", "cartpole_smoke", "--connect", f"127.0.0.1:{port}",
         "--actors", "1", "--actor-offset", str(offset),
         "--frames-per-actor", str(frames),
         "--set", "actors.ingest_batch=16",
         "--set", "inference.deadline_ms=1.0"],
        env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def test_two_process_training_over_tcp():
    """A remote actor host (separate OS process) feeds the learner over
    the socket transport and pulls params; training proceeds on the
    combined experience stream."""
    cfg = _learner_cfg(num_local_actors=1)
    server = SocketIngestServer("127.0.0.1", 0)
    # constructing the driver publishes params v0, which the remote host
    # blocks on — so the remote can run its whole 300-frame budget before
    # the timed local run starts; its ~19 batches of 16 park in the ingest
    # queue (max_pending=64) and drain when run() begins. This removes the
    # race between remote JAX startup (~10s import) and the local budget.
    driver = ApexDriver(cfg, transport=server)
    proc = _spawn_actor_host(server.port, frames=300)
    try:
        stdout, stderr = proc.communicate(timeout=120)
        assert proc.returncode == 0, stderr[-2000:]
        assert "'errors': []" in stdout
        assert server.pending > 0, "remote experience never reached the queue"
        out = driver.run(total_env_frames=4000, max_grad_steps=10**9,
                         wall_clock_limit_s=240)
        assert out["actor_errors"] == [], out["actor_errors"]
        assert out["loop_errors"] == [], out["loop_errors"]
        assert out["grad_steps"] > 0, out
        # drop-accounting closure, not an exact frame count: the old
        # `frames > 4050` was load-flaky — a contended host legitimately
        # drops bounded-queue messages, and those frames are not lost,
        # they are COUNTED. Every produced frame is either ingested
        # (out["frames"]), inside a dropped queue message (server.dropped
        # messages of <= ingest_batch frames each), or in the staged
        # sub-block tail discarded at teardown (_stage_dropped,
        # frame-denominated in flat mode). The closure still fails if
        # the remote stream silently vanishes without being accounted.
        accounted = (out["frames"]
                     + server.dropped * cfg.actors.ingest_batch
                     + driver._stage_dropped)
        assert accounted > 4050, (out["frames"], server.dropped,
                                  driver._stage_dropped)
    finally:
        if proc.poll() is None:
            proc.kill()
        server.stop()


_R2D2_SETS = [
    "env.kind=cartpole_po", "env.id=CartPolePO",
    "replay.storage=flat",  # preset is frame_ring, needs pixel obs
    "network.lstm_size=32", "network.torso_dense=64",
    "network.compute_dtype=float32",
    "replay.capacity=512", "replay.seq_length=16", "replay.seq_overlap=8",
    "replay.burn_in=4", "replay.min_fill=24",
    "learner.batch_size=16", "learner.publish_every=20",
    "learner.train_chunk=4",
    "actors.ingest_batch=64", "inference.max_batch=8",
    "inference.deadline_ms=1.0",
    "parallel.dp=1", "parallel.tp=1",
    "eval_every_steps=0", "eval_episodes=0",
]


def test_two_process_r2d2_training_over_tcp():
    """A remote RECURRENT actor host feeds stored-state sequences over
    the socket transport (runtime/family.py dispatch shared with the
    driver); the sequence learner trains on the combined stream."""
    from ape_x_dqn_tpu.runtime.train import apply_overrides

    cfg = apply_overrides(get_config("r2d2"), _R2D2_SETS)
    cfg = cfg.replace(actors=dataclasses.replace(cfg.actors, num_actors=1))
    server = SocketIngestServer("127.0.0.1", 0)
    driver = ApexDriver(cfg, transport=server)  # publishes params v0
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-m", "ape_x_dqn_tpu.runtime.actor_host",
         "--config", "r2d2", "--connect", f"127.0.0.1:{server.port}",
         "--actors", "1", "--actor-offset", "1",
         "--frames-per-actor", "400"]
        + [a for s in _R2D2_SETS for a in ("--set", s)],
        env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
        stdout, stderr = proc.communicate(timeout=180)
        assert proc.returncode == 0, stderr[-2000:]
        assert "'errors': []" in stdout
        assert server.pending > 0, "remote sequences never reached the queue"
        out = driver.run(total_env_frames=2000, max_grad_steps=10**9,
                         wall_clock_limit_s=240)
        assert out["actor_errors"] == [], out["actor_errors"]
        assert out["loop_errors"] == [], out["loop_errors"]
        assert out["grad_steps"] > 0, out
        # the remote host's 400 frames arrived on top of the local 2000
        assert out["frames"] > 2100, out
    finally:
        if proc.poll() is None:
            proc.kill()
        server.stop()


def test_dpg_remote_actor_host_ships_continuous_experience():
    """The DPG family over the remote-host path (runtime/family.py):
    {actor, critic} params distribute through the transport's pickle
    channel, the host's server evaluates {a: mu(s), q: Q(s, mu(s))},
    and ContinuousActor ships float-action transitions."""
    from ape_x_dqn_tpu.configs import get_config as _get
    from ape_x_dqn_tpu.runtime.actor_host import run_actor_host
    from ape_x_dqn_tpu.runtime.driver import ApexDriver as _Driver

    cfg = _get("apex_dpg").replace(
        env=dataclasses.replace(_get("apex_dpg").env,
                                id="pendulum", kind="control"),
        actors=ActorConfig(num_actors=1, ingest_batch=16,
                           noise_sigma=0.15),
        inference=InferenceConfig(max_batch=4, deadline_ms=1.0),
        eval_every_steps=0, eval_episodes=0,
    )
    server = SocketIngestServer("127.0.0.1", 0)
    driver = _Driver(cfg, transport=server)  # publishes dpg params v0
    try:
        out = run_actor_host(cfg, "127.0.0.1", server.port, num_actors=1,
                             actor_offset=1, frames_per_actor=120)
        assert out["errors"] == [], out["errors"]
        assert out["frames"] == 120
        assert out["last_param_version"] >= 0
        got = server.recv_experience(timeout=5.0)
        assert got is not None
        assert got["action"].dtype == np.float32  # continuous actions
        assert got["action"].ndim == 2            # [B, action_dim]
        assert (got["priorities"] >= 0).all()
    finally:
        driver.server.stop()
        server.stop()


def test_remote_only_learner_waits_then_quiesces():
    """A learner with ZERO local actors (the soak/deployment topology)
    must (a) survive the window before any actor host connects (boot
    grace), (b) train on late-arriving remote experience, and (c)
    self-terminate via the QUIESCE path once the remote disconnects and
    the grace window passes — instead of either exiting at t=0 or
    spinning forever. max_grad_steps stays at the 10**9 sentinel, so
    only (c) can end the run before the wall-clock limit."""
    cfg = _learner_cfg(num_local_actors=0).replace(
        actors=ActorConfig(num_actors=0, remote_boot_grace_s=60.0),
        learner=LearnerConfig(batch_size=32, n_step=3,
                              target_sync_every=100, publish_every=20,
                              train_chunk=4))
    server = SocketIngestServer("127.0.0.1", 0, idle_grace_s=1.0)
    driver = ApexDriver(cfg, transport=server)

    def late_remote():
        time.sleep(1.5)  # the learner must still be waiting
        client = SocketTransport("127.0.0.1", server.port)
        rng = np.random.default_rng(0)
        for _ in range(10):
            n = 32
            client.send_experience({
                "obs": rng.normal(size=(n, 4)).astype(np.float32),
                "action": rng.integers(0, 2, n).astype(np.int32),
                "reward": rng.normal(size=n).astype(np.float32),
                "next_obs": rng.normal(size=(n, 4)).astype(np.float32),
                "discount": np.full(n, 0.97, np.float32),
                "priorities": rng.random(n).astype(np.float32) + 0.1,
                "actor": 0, "frames": n,
            })
        time.sleep(0.5)  # let the reader drain before the socket dies
        client.close()

    t = threading.Thread(target=late_remote, daemon=True)
    t.start()
    try:
        out = driver.run(total_env_frames=10**9, max_grad_steps=10**9,
                         wall_clock_limit_s=120)
        t.join(timeout=10)
        assert out["loop_errors"] == [], out["loop_errors"]
        # (a)+(b): the boot grace held the learner alive long enough to
        # ingest the late remote's 320 transitions and train on them
        assert out["grad_steps"] > 0, out
        assert out["frames"] >= 64, out
        # (c): with no finite step target, only the quiesce/stuck path
        # can end the run this early — a regression that spins forever
        # would hit the 120s wall clock instead
        assert out["wall_s"] < 60, out
    finally:
        server.stop()


def test_actor_loss_fault_injection():
    """SURVEY.md §5: killing an actor host mid-run must not disturb the
    learner — training reaches its target with no errors."""
    cfg = _learner_cfg(num_local_actors=1)
    server = SocketIngestServer("127.0.0.1", 0)
    driver = ApexDriver(cfg, transport=server)
    proc = _spawn_actor_host(server.port, frames=10**7)  # would run forever
    # the kill rides an event the driver already reports, not a clock:
    # the learner thread hands the server its first params published
    # after training began (publish_every=20), and the host dies right
    # there, so every later grad step strictly follows the kill. (A
    # thread polling driver.grad_steps cannot see this run mid-way: the
    # 60 steps take less than one 50 ms poll.)
    killed_at = []
    publish = server.publish_params

    def publish_then_kill(params, version):
        publish(params, version)
        if version > 0 and not killed_at:
            proc.send_signal(signal.SIGKILL)
            proc.wait()
            killed_at.append(version)

    server.publish_params = publish_then_kill
    try:
        # params v0 went out when the driver was built, so the host acts
        # and ships as soon as its interpreter is up (seconds); wait for
        # its first experience, or the host is not yet a producer when
        # it dies. Its batches park in the ingest queue until run().
        deadline = time.monotonic() + 120
        while not server.ever_connected and time.monotonic() < deadline:
            assert proc.poll() is None, proc.stderr.read()
            time.sleep(0.05)
        assert server.ever_connected, "actor host never delivered"
        out = driver.run(total_env_frames=1500, max_grad_steps=60,
                         wall_clock_limit_s=180)
        assert proc.poll() is not None, "actor host was not killed"
        assert killed_at and killed_at[0] < 60, killed_at  # mid-run
        assert out["actor_errors"] == [], out["actor_errors"]
        assert out["loop_errors"] == [], out["loop_errors"]
        assert out["grad_steps"] >= 60, out
    finally:
        if proc.poll() is None:
            proc.kill()
        server.stop()


def test_param_only_probe_is_not_a_producer():
    """ever_connected must latch on the first EXPERIENCE message, not
    on accept: a param-only client (monitoring probe, or an actor host
    that died waiting for params) that comes and goes during learner
    construction would otherwise skip the boot grace AND read as a
    departed producer — observed terminating a remote-only learner
    0.1s into its run (round-4 soak)."""
    server = SocketIngestServer("127.0.0.1", 0)
    client = SocketTransport("127.0.0.1", server.port)
    try:
        server.publish_params({"w": np.ones(2, np.float32)}, 1)
        params, _ = client.get_params()   # param-only connection
        assert params is not None
        client.close()
        time.sleep(0.3)
        assert server.ever_connected is False  # probe, not producer

        client2 = SocketTransport("127.0.0.1", server.port)
        client2.send_experience({"obs": np.zeros((2, 2), np.float32),
                                 "priorities": np.ones(2, np.float32),
                                 "frames": 2})
        got = server.recv_experience(timeout=5.0)
        assert got is not None
        assert server.ever_connected is True   # real producer
        client2.close()
    finally:
        client.close()
        server.stop()


def test_param_probe_does_not_end_learner_boot_grace():
    """A remote-only learner (0 local actors) must hold its full boot
    grace even when a param-only client touches the listener: probes
    polled active_connections into saw_remote and the learner
    self-terminated 88s into a 300s grace (observed live, round 4)."""
    from ape_x_dqn_tpu.configs import get_config

    cfg = get_config("cartpole_smoke").replace(
        actors=ActorConfig(num_actors=0, remote_boot_grace_s=4.0),
        replay=ReplayConfig(kind="prioritized", capacity=512, min_fill=64),
        learner=LearnerConfig(batch_size=16, publish_every=20),
        inference=InferenceConfig(max_batch=4, deadline_ms=1.0),
        eval_every_steps=0, eval_episodes=0)
    server = SocketIngestServer("127.0.0.1", 0)
    driver = ApexDriver(cfg, transport=server)
    probe = SocketTransport("127.0.0.1", server.port)
    t_run = {}

    def run():
        t0 = time.monotonic()
        driver.run(total_env_frames=10**9, max_grad_steps=10**9,
                   wall_clock_limit_s=20.0)
        t_run["wall"] = time.monotonic() - t0

    th = threading.Thread(target=run, daemon=True)
    th.start()
    # poke the listener with param-only pulls through the grace window
    for _ in range(6):
        probe.get_params()
        time.sleep(0.25)
    probe.close()
    th.join(timeout=60)
    assert not th.is_alive(), "driver.run never returned"
    # the run must have survived at least the grace (it exits when the
    # grace lapses with no producer, NOT when the probe disconnects)
    assert t_run["wall"] >= 3.5, t_run
    server.stop()
