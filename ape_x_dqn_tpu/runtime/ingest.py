"""Zero-copy pipelined ingest staging (actor wire -> device replay).

The driver's one staging path: preallocated fixed-shape staging
buffers, so no received batch is appended to a list and concatenated
again per flush.

- Wire batches decode DIRECTLY into a contiguous staging row at a write
  cursor (comm/socket_transport.decode_batch_into): ONE copy per wire
  byte, contiguous by construction. Contiguity is what device_put speed
  lives on — PERF.md round 5 measured ~80 vs ~3,000 items/s between a
  fragmented and a contiguous host source.
- Double buffering: while buffer N's async device_put is in flight,
  the next batches decode into buffer N+1; the stager blocks on the
  in-flight handles only when it is about to overwrite that memory.
- Coalescing: a buffer holds `coalesce` fixed-size blocks; a FULL
  buffer ships as one `add_many` dispatch (g blocks, one donated jit,
  one _state_lock acquisition) instead of g small adds interleaving
  with the learner's train_many dispatches.

Shapes are fixed by construction (block = dp * stage_chunk units,
buffer = coalesce blocks), so the device sees exactly two add graphs:
the warmed single-block `add` (idle drains, see below) and the warmed
`add_many` at g = coalesce. Ragged shapes would each compile a fresh
XLA graph (20-40s on TPU).

Latency bound: the driver calls drain() whenever the transport queue
runs dry (its 0.1s recv timeout), which ships every COMPLETE block in
the partial buffer block-by-block through the warmed `add` graph and
compacts the remainder to the buffer front — so coalescing never holds
experience hostage behind a slow actor stream. The sub-block tail only
drops (counted by the driver, in its three denominations) at
force-flush during teardown.
"""

from __future__ import annotations

import time
from typing import Callable

import jax
import numpy as np

# ship(views, g): views is {key: np.ndarray of [g*block_units, ...]}
# including "priorities"; g is the number of coalesced blocks. Returns
# the device-side handles of the asynchronous host->device transfer;
# the stager blocks on them before reusing the staging memory.
ShipFn = Callable[[dict, int], list]


class IngestStager:
    def __init__(self, item_spec: dict, ptail: tuple, block_units: int,
                 coalesce: int, buffers: int, ship: ShipFn):
        """item_spec: {key: spec with .shape/.dtype} for one staging
        unit; ptail: trailing priority axes ((seg_transitions,) in
        frame-ring mode, () otherwise); block_units: dp * stage_chunk
        units per device add; coalesce: blocks fused per full-buffer
        add_many; buffers: staging buffers to rotate (>= 2 gives the
        decode/transfer overlap)."""
        self.block = int(block_units)
        self.coalesce = max(int(coalesce), 1)
        self.rows = self.block * self.coalesce
        self.nb = max(int(buffers), 1)
        self._ship = ship
        self._keys = tuple(item_spec.keys()) + ("priorities",)
        shapes = {k: tuple(s.shape) for k, s in item_spec.items()}
        dtypes = {k: s.dtype for k, s in item_spec.items()}
        shapes["priorities"] = tuple(ptail)
        dtypes["priorities"] = np.float32
        self._bufs = [
            {k: np.zeros((self.rows,) + shapes[k], dtypes[k])
             for k in self._keys}
            for _ in range(self.nb)]
        self._inflight: list[list] = [[] for _ in range(self.nb)]
        self._active = 0
        self._cursor = 0  # rows staged in the active buffer
        # wire-codec decode accounting: cumulative wall-ms spent inside
        # decode_into/dict landing (inflate + delta-undo + the one copy)
        # — obs surfaces it as ingest_decode_ms per put
        self.decode_ms = 0.0
        self.last_put_decode_ms = 0.0
        # ship-side accounting: host wall-ms spent inside the ship
        # callback (device_put enqueue + the donated add dispatch under
        # _state_lock — NOT device execution time; the sampled roofline
        # windows in the driver measure that). A last_ship_ms creeping
        # toward the decode budget means the "async" transfer path has
        # started blocking, i.e. the overlap is lost
        self.ship_ms = 0.0
        self.last_ship_ms = 0.0
        # cross-process correlation: tags (e.g. (peer, batch_id) from
        # the wire header) of batches staged since the last ship; the
        # ship callback reads `shipping_tags` to attribute the device
        # dispatch. Approximate by design — a batch that straddles a
        # buffer boundary is attributed to the ship that took its head
        self._pending_tags: list = []
        self.shipping_tags: tuple = ()

    # -- write side --------------------------------------------------------

    def _wait(self, i: int) -> None:
        """Block until buffer i's previous host->device transfer is done
        — only then may its host memory be rewritten. With >= 2 buffers
        this almost never actually waits (the transfer overlapped the
        previous buffer's decode)."""
        if self._inflight[i]:
            jax.block_until_ready(self._inflight[i])  # apexlint: host-sync(deliberate reuse barrier: memory rewritten only after its transfer lands)
            self._inflight[i] = []

    def put(self, batch, tag=None) -> None:
        """Stage one ingest message (WireBatch or plain dict of arrays),
        splitting across buffer boundaries; full buffers ship as one
        coalesced add_many. `tag` is an opaque correlation handle
        surfaced via `shipping_tags` on the ship that carries it."""
        if tag is not None:
            self._pending_tags.append(tag)
        wire = hasattr(batch, "decode_into")
        total = batch.rows if wire \
            else int(batch["priorities"].shape[0])
        start = 0
        put_ms = 0.0
        while start < total:
            self._wait(self._active)
            buf = self._bufs[self._active]
            k = min(total - start, self.rows - self._cursor)
            t0 = time.perf_counter()
            if wire:
                batch.decode_into(buf, self._cursor, start, k)
            else:
                for key in self._keys:
                    buf[key][self._cursor:self._cursor + k] = \
                        np.asarray(batch[key])[start:start + k]  # apexlint: host-sync(wire batch is host numpy, not a device value)
            put_ms += (time.perf_counter() - t0) * 1e3
            self._cursor += k
            start += k
            if self._cursor == self.rows:
                self._ship_buffer()
        self.last_put_decode_ms = put_ms
        self.decode_ms += put_ms
        # shm slot batches alias a shared-memory ring slot; releasing
        # after the staging land returns the slot to the actor's
        # free-list (plain WireBatch/dict have no release — no-op)
        rel = getattr(batch, "release", None)
        if rel is not None:
            rel()

    def _ship_buffer(self) -> None:
        """Full buffer -> one add_many dispatch; rotate to the next
        buffer while the transfer flies."""
        buf = self._bufs[self._active]
        self.shipping_tags = tuple(self._pending_tags)
        self._pending_tags = []
        t0 = time.perf_counter()
        self._inflight[self._active] = list(
            self._ship({k: buf[k] for k in self._keys}, self.coalesce))
        self.last_ship_ms = (time.perf_counter() - t0) * 1e3
        self.ship_ms += self.last_ship_ms
        self._active = (self._active + 1) % self.nb
        self._cursor = 0

    # -- drain / teardown --------------------------------------------------

    def drain(self) -> int:
        """Ship every COMPLETE block in the partial active buffer
        through the warmed single-block add graph (g=1 keeps the graph
        count fixed: partial groups at every g in [1, coalesce) would
        each compile fresh). Remainder rows compact to the buffer front.
        Called by the driver whenever the transport queue runs dry, so
        coalescing costs bounded latency. Returns blocks shipped."""
        nblocks = self._cursor // self.block
        if nblocks == 0:
            return 0
        buf = self._bufs[self._active]
        shipped = nblocks * self.block
        self.shipping_tags = tuple(self._pending_tags)
        self._pending_tags = []
        handles: list = []
        t0 = time.perf_counter()
        for b in range(nblocks):
            views = {k: buf[k][b * self.block:(b + 1) * self.block]
                     for k in self._keys}
            handles += list(self._ship(views, 1))
        self.last_ship_ms = (time.perf_counter() - t0) * 1e3
        self.ship_ms += self.last_ship_ms
        rem = self._cursor - shipped
        if rem:
            # the shipped region becomes the compaction destination:
            # wait for its transfer before overwriting. Non-overlapping
            # copy: rem < block <= shipped.
            jax.block_until_ready(handles)  # apexlint: host-sync(compaction barrier: shipped region is the copy destination)
            for k in self._keys:
                buf[k][:rem] = buf[k][shipped:self._cursor]
        else:
            self._inflight[self._active] = handles
        self._cursor = rem
        return nblocks

    def tail_units(self) -> int:
        """Staged rows that cannot form a complete block (valid after
        drain()); the driver's force-flush drop accounting reads this."""
        return self._cursor

    def tail_view(self, key: str) -> np.ndarray:
        """View of the staged sub-block tail for `key` (e.g. frame-ring
        drop accounting counts live transitions via next_off)."""
        return self._bufs[self._active][key][:self._cursor]

    def tail_shard_units(self, dp: int) -> list[int]:
        """Unit count of the current sub-block tail per dp shard under
        the driver's round-robin block split: a shipped block reshapes
        [block] -> [dp, chunk] (chunk = block // dp) in C order, so
        tail unit i would have landed on shard i // chunk. The driver's
        per-shard drop closure (`sum(per_shard) == dropped`, pinned by
        tests/test_ingest.py) folds these counts into whichever
        denomination the storage family drops in."""
        chunk = self.block // max(dp, 1)
        tail = self.tail_units()
        return [max(0, min(tail - d * chunk, chunk)) for d in range(dp)]

    def discard_tail(self) -> None:
        self._cursor = 0

    def occupancy(self) -> float:
        """Fill fraction of the active staging buffer (obs gauge)."""
        return self._cursor / self.rows

    def free_units(self) -> int:
        """Rows the active buffer absorbs before a put triggers the
        coalesced ship. The cold tier's idle refill tick bounds its
        recall/promotion burst to this so restaging recalled segments
        never forces a synchronous mid-idle add_many dispatch (which
        would take _state_lock against train_many — the contention the
        idle tick exists to avoid)."""
        return self.rows - self._cursor
