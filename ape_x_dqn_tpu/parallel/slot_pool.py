"""The host's ledger of a slot server's shared pool
(parallel/inference_server.py, the slot path): which contiguous range of
key blocks each live session owns, and how many positions of it are
used. The device holds the blocks (models/minicpm_sala_q.py
`slot_state`); this file only hands ranges out. What a range holds is
the net's: MiniCPM-SALA's sparse layers keep keys, values and compressed
keys there beside a row of lightning matrices a slot; a net's state may
hold NO blocks at all for most layers (models/jamba_q.py: 26 Mamba
layers keep one row a slot at any context, and the ledger's ranges are
its two attention layers' keys and values alone).

A session is admitted with the longest length it DECLARES and gets that
many positions' blocks, first fit, in one piece: no slot is padded to
the longest session, and the program addresses a session's blocks as
`base + i` with no table on the device. A slot's next admission frees
what it held. What this is not (ROADMAP R2.1 keeps them): a free-list
pager of scattered blocks, eviction of a live session, sharing of a
common prefix. Only the serve thread touches a pool.
"""

from __future__ import annotations


class SlotPoolFull(RuntimeError):
    """No free range of the pool is long enough for the session that
    asked to be admitted; the query that asked fails with this."""


class SlotOverflow(RuntimeError):
    """A session sent more positions than it declared (or was never
    admitted); the query fails and the session's state is unchanged."""


class SlotStateLost(RuntimeError):
    """A dispatch failed after the device had taken the donated state:
    the server zeroed it and every session that was live then is gone;
    such a session's next query fails with this until it begins again
    (`fresh`)."""


class SlotPool:
    def __init__(self, slots: int, pool_blocks: int, block: int,
                 max_len: int):
        """`slots` sessions share `pool_blocks` blocks of `block`
        positions; none may declare more than `max_len` positions, which
        is also what one that declares nothing gets. Slot `slots` and
        the blocks from `pool_blocks` on are the scratch a padding row
        uses."""
        self.slots, self.pool_blocks = int(slots), int(pool_blocks)
        self.block, self.max_len = int(block), int(max_len)
        self.scratch_slot, self.scratch_base = self.slots, self.pool_blocks
        self._ranges: dict[int, list[int]] = {}   # slot: [base, blocks, used]
        self._lost: set[int] = set()    # live when the state was lost

    @property
    def live(self) -> int:
        return len(self._ranges)

    @property
    def blocks_held(self) -> int:
        return sum(r[1] for r in self._ranges.values())

    def base(self, slot: int) -> int:
        return self._ranges[slot][0]

    def held(self, slot: int) -> tuple | None:
        """What `slot` holds, to `restore` it by."""
        held = self._ranges.get(slot)
        return None if held is None else tuple(held)

    def restore(self, slot: int, held: tuple | None) -> None:
        self._ranges.pop(slot, None)
        if held is not None:
            self._ranges[slot] = list(held)

    def snapshot(self) -> dict:
        """Every live session's range, to `reset` the ledger by."""
        return {slot: tuple(r) for slot, r in self._ranges.items()}

    def reset(self, snapshot: dict) -> None:
        self._ranges = {slot: list(r) for slot, r in snapshot.items()}

    def lose_all(self) -> None:
        """The device's state is gone: no session is live, and those
        that were say so by name when they next ask (`advance`)."""
        self._lost |= set(self._ranges)
        self._ranges.clear()

    def free(self, slot: int) -> int:
        """-> the blocks `slot` gave back (0: it held none)."""
        held = self._ranges.pop(slot, None)
        return held[1] if held else 0

    def admit(self, slot: int, declared: int = 0) -> int:
        """A new session in `slot` (what the slot held is freed) of at
        most `declared` positions -> its first block."""
        if not 0 <= slot < self.slots:
            raise SlotOverflow(f"slot {slot} of {self.slots}")
        declared = declared or self.max_len
        if declared > self.max_len:
            raise SlotOverflow(
                f"slot {slot} declares {declared} positions; the server "
                f"was built for {self.max_len} (inference.slot_max_len)")
        self.free(slot)
        self._lost.discard(slot)
        need = -(-declared // self.block)
        at = 0
        for base, blocks, _ in sorted(self._ranges.values()):
            if base - at >= need:
                break
            at = base + blocks
        if at + need > self.pool_blocks:
            raise SlotPoolFull(
                f"slot {slot} declares {declared} positions ({need} "
                f"blocks) and no free range of the pool's "
                f"{self.pool_blocks} blocks is that long "
                f"({self.blocks_held} held by {self.live} sessions)")
        self._ranges[slot] = [at, need, 0]
        return at

    def advance(self, slot: int, positions: int) -> None:
        """`slot`'s session grows by `positions`."""
        held = self._ranges.get(slot)
        if held is None and slot in self._lost:
            raise SlotStateLost(
                f"slot {slot}'s session was live when a dispatch failed "
                f"with the state on the device: it has to begin again")
        if held is None:
            raise SlotOverflow(f"slot {slot} was never admitted (an "
                               f"episode's first query says `fresh`)")
        if held[2] + positions > held[1] * self.block:
            raise SlotOverflow(
                f"slot {slot} holds {held[2]} positions and sends "
                f"{positions} more, past the {held[1] * self.block} it "
                f"declared")
        held[2] += positions
