"""Transport layer: actor -> replay ingest and learner -> actor params.

The reference moves experience and parameters over gRPC and does learner
collectives over NCCL (SURVEY.md §2.2 "Comm"). The TPU-native mapping
(SURVEY.md §5 "distributed communication backend"):

- learner-internal collectives: XLA psum/all-gather over ICI (see
  parallel/dist_learner.py) — nothing to do here.
- learner -> inference-server weight publication: device-to-device
  resharding over ICI (DistLearner.publish_params).
- actor <-> inference server and actor -> replay ingest: host-side
  message passing. In-process that's thread-safe queues (the
  `LoopbackTransport` below, also the deterministic test harness per
  SURVEY.md §4); across hosts the same interface runs over TCP sockets
  (`comm.socket_transport`) riding DCN.

Messages are pytrees of numpy arrays; an ingest message is a dict with
stacked transition fields plus "priorities".

A third, low-rate path rides the same interface: fleet telemetry.
`send_telemetry(frame)` ships a compact per-peer obs snapshot (JSON
dict); the receiving side exposes an `on_telemetry(peer_id, frame)`
hook the driver's fleet aggregator installs. On loopback the frame is
handed to the hook directly; over sockets it becomes MSG_TELEMETRY and
is subject to hello/ack capability negotiation (old peers drop it
cleanly — see comm.socket_transport).
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Protocol


class Transport(Protocol):
    def send_experience(self, batch: dict) -> None: ...
    def recv_experience(self, timeout: float | None = None) -> dict | None: ...
    def publish_params(self, params: Any, version: int) -> None: ...
    def get_params(self) -> tuple[Any, int]: ...
    def send_telemetry(self, frame: dict) -> bool: ...


class LoopbackTransport:
    """In-process transport: bounded queue + versioned param cell."""

    def __init__(self, max_pending: int = 256):
        # the bound is in messages, what it buys is TIME: how long
        # ingest may be away from the queue before the oldest message
        # is dropped. A fleet behind the pipelined inference server
        # ships ~1,400 one-segment messages a second, and ingest is
        # away ~10 ms for every coalesced staging buffer it lands under
        # a busy GIL, eight in a row while a ring is being filled
        # (PERF.md section 6, PR 40: 64 was 45 ms there and overflowed,
        # a queue of 128 still peaked at 120); 256 is ~180 ms
        self._q: queue.Queue[dict] = queue.Queue(maxsize=max_pending)
        self._params: Any = None
        self._version = -1
        self._lock = threading.Lock()
        self._dropped = 0
        self._telemetry_frames = 0
        # fleet hook (set by the driver); called inline from the sender
        self.on_telemetry: Any = None  # (peer_id: str, frame: dict) -> None

    # experience path (actor -> replay ingest)

    def send_experience(self, batch: dict) -> None:
        """Non-blocking; drops oldest under backpressure (actors must
        never stall the env loop — matches Ape-X semantics where replay
        ingest is lossy-tolerant)."""
        while True:
            try:
                self._q.put_nowait(batch)
                return
            except queue.Full:
                try:
                    self._q.get_nowait()
                    self._dropped += 1
                except queue.Empty:
                    pass

    def recv_experience(self, timeout: float | None = None) -> dict | None:
        try:
            return self._q.get(timeout=timeout)
        except queue.Empty:
            return None

    @property
    def dropped(self) -> int:
        return self._dropped

    @property
    def pending(self) -> int:
        return self._q.qsize()

    def close(self) -> None:
        """Drain parked batches so their (potentially large) arrays
        are not pinned by a queue nobody will read again — loopback
        holds no OS handles, but drivers call close() on every
        transport symmetrically."""
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                return

    # parameter path (learner -> actors/server)

    def publish_params(self, params: Any, version: int) -> None:
        with self._lock:
            self._params = params
            self._version = version

    def get_params(self) -> tuple[Any, int]:
        with self._lock:
            return self._params, self._version

    # telemetry path (peer obs snapshots -> fleet aggregator)

    def send_telemetry(self, frame: dict) -> bool:
        """In-process delivery straight to the aggregator hook; True
        iff a hook was installed (mirrors the socket transport's
        negotiated/not-negotiated return)."""
        cb = self.on_telemetry
        if cb is None:
            return False
        with self._lock:
            self._telemetry_frames += 1
        cb(str(frame.get("peer", "peer?")), frame)
        return True

    @property
    def telemetry_frames(self) -> int:
        with self._lock:
            return self._telemetry_frames
