"""Tile-exact pixel packing + in-place ring writes for HBM replay.

Why this module exists (round-5 HBM story, PERF.md "HBM budget"):

TPU HBM arrays are stored in (8, 128)-tiled layouts — for uint8 the
effective tile is (32, 128) over the two minor dimensions. A pixel
buffer shaped [capacity, 84, 84] therefore pads 84 -> (88, 128) and
occupies **1.6x** its logical bytes. Worse, XLA then assigns the
*parameter* a compact (unpadded) layout to save that memory and inserts
a full-buffer relayout copy inside every program that gathers from or
scatters into it: measured on the v5e chip, the pong preset's 9.47GB
frame ring compiled to a 15.12GB HLO temp copy inside `add` (25.1GB
total — OOM on a 15.75GB chip).

Two design rules eliminate both costs:

1. **Pack pixel leaves into exactly-tiled rows of 32-bit words.**
   Store [capacity, pad_row(prod(frame_dims)) // 4] uint32, word k
   holding bytes 4k..4k+3 of the row, least significant first — the
   minor dim a multiple of 128 words and the major dim a multiple of 8
   makes the padded tiled layout bit-identical to the compact layout,
   so no relayout copy can exist anywhere, and the storage overhead is
   the row padding alone (1.6% for an 84x84 frame: 7056 -> 7168 bytes
   = 14 x 128 words). Unpacking after a sample's row gather touches
   only the sampled batch (MBs, not GBs).

   Which stores keep BYTE rows: none. A uint8 array's tile packs four
   ROWS into one 32-bit word (T(8,128)(4,1)), so a row gather from a
   uint8 buffer fetches four rows for every one it returns: the frame
   ring's gather went 86 -> 25 ns a row when its rows became words
   (replay/frame_ring.py, PR 29), R2D2's sequence gather 70 -> 25
   (the fusion alone; with the relayout of what it gathered, the whole
   `replay.sample_gather` scope, 117 -> 33) when the packed stores of
   this module followed (PixelPacker, PR 42; PERF.md §6). The cost
   belongs to the dtype's tile and not to a workload, so every
   packable leaf is stored as words: flat DQN transitions, R2D2's
   sequences. Bytes become words
   inside the add jit (`as_words`) and bytes again only in what a
   sample or `read_region` hands out (`as_bytes`); what leaves the
   device — cold segments, replay-bearing checkpoints — is bytes.
   dus_rows below serves every store and stays dtype-agnostic.

2. **Ring writes are `dynamic_update_slice`, never scatter.** A
   scatter into a large donated buffer still materializes a full copy
   (measured: 19.1GB for the 9.47GB 2-D ring); a dynamic_update_slice
   on a donated argument aliases in place (measured: temp=0). Since a
   replay add always writes a contiguous index block, the only case
   DUS cannot express is a block wrapping the ring boundary — handled
   by SKIP-TO-HEAD semantics: a block that would wrap is written at
   slot 0 instead, leaving the few tail slots holding their previous
   (still-consistent) items. When the block size divides the capacity
   the wrap case never occurs and semantics are bit-identical to the
   modular ring — which covers the frame-ring/segment ingest paths
   (fixed-size segment blocks, capacity a multiple of the segment
   size) but NOT every flat-transition path: the default
   ActorConfig.ingest_batch=50 does not divide a power-of-two
   capacity, and a shutdown flush ships whatever partial block
   remains. For such non-dividing block sizes, every skip restarts
   the cursor at slot 0 and up to block-1 tail slots are permanently
   RETIRED: ring_write_size never counts them as filled, the sum-tree
   never carries priority there, and sampling never returns them
   (regression-tested in tests/test_packing.py with ingest_batch=50)
   — a <= block/capacity capacity loss, not a correctness hazard.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# Minor-dim tile width (lanes) shared by every TPU dtype; uint32 arrays
# additionally want the second-minor dim a multiple of 8 (sublanes) for
# the padded layout to equal the compact one.
LANE = 128
WORD = 4    # bytes in one 32-bit word


def pad128(n: int) -> int:
    """Round up to the 128-byte lane tile."""
    return -(-int(n) // LANE) * LANE


def pad_row(n: int) -> int:
    """Round a row's bytes up to whole lane tiles of WORDS (512 B)."""
    return -(-int(n) // (LANE * WORD)) * LANE * WORD


# -- bytes <-> 32-bit words (every word-row store: this module's packer,
# replay/frame_ring.py) --------------------------------------------------


def as_words(rows: jax.Array, strided: bool = False) -> jax.Array:
    """uint8 [..., 4n] -> uint32 [..., n]: word k holds bytes
    4k..4k+3, least significant first. Written as shifts and ORs, not
    as `bitcast_convert_type` of a [..., n, 4] view: the two give the
    same words, but XLA:TPU lays the bitcast's result out rows-minor
    and, in the per-shard directed write on the mesh, then copies the
    whole ring to that layout rather than the block to the ring's
    (add_at_lockstep at flagship size: 9.6 GB of HLO temp, out of
    memory; as arithmetic every write keeps the byte rows' temp).

    `strided`: byte i of every word as the stride-4 slice `[i::4]` of
    the rows, widened after the slice — the same words again. The
    view's form widens the whole block first: for the packed store's
    block of 64 R2D2 sequences (38 MB) `add` compiled to 294 MiB of
    HLO temp, held by every add in flight, and the cell's peak HBM
    rose 1.2%; sliced first it compiles to 0 (PERF.md §6, PR 42). The
    frame ring's blocks are a fifteenth of that and its programs are
    pinned on the view's form."""
    if strided:
        b = [rows[..., i::WORD].astype(jnp.uint32) for i in range(WORD)]
        return b[0] | (b[1] << 8) | (b[2] << 16) | (b[3] << 24)
    b = rows.reshape(*rows.shape[:-1], -1, WORD).astype(jnp.uint32)
    return b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16) | (b[..., 3] << 24)


def as_bytes(words: jax.Array) -> jax.Array:
    """uint32 [..., n] -> uint8 [..., 4n], the inverse of as_words."""
    return jax.lax.bitcast_convert_type(words, jnp.uint8).reshape(
        *words.shape[:-1], -1)


def byte_transpose(w) -> list[jax.Array]:
    """Four uint32 arrays (`w[0]`..`w[3]`) -> four: byte f of out[i]
    is byte i of w[f] — a 4x4 transpose of the bytes held at one
    position, as two rounds of mask-shift-OR on whole words (16-bit
    halves between w[0]/w[2] and w[1]/w[3], then bytes between the
    pairs). out[i] is pixel 4k+i of each of the four frames, packed in
    the order a [..., stack] uint8 array keeps them."""
    lo, hi = jnp.uint32(0x0000FFFF), jnp.uint32(0xFFFF0000)
    even, odd = jnp.uint32(0x00FF00FF), jnp.uint32(0xFF00FF00)
    t0 = (w[0] & lo) | (w[2] << 16)
    t1 = (w[1] & lo) | (w[3] << 16)
    t2 = (w[0] >> 16) | (w[2] & hi)
    t3 = (w[1] >> 16) | (w[3] & hi)
    return [(t0 & even) | ((t1 & even) << 8), ((t0 >> 8) & even) | (t1 & odd),
            (t2 & even) | ((t3 & even) << 8), ((t2 >> 8) & even) | (t3 & odd)]


def stacks_of_four(w, pixels: int) -> jax.Array:
    """Four uint32 [cols, row] arrays, the word rows of four
    consecutive frames -> uint8 [pixels, cols, 4], each pixel's
    four-frame stack with the columns in the lanes: the byte transpose,
    each result transposed as a 32-bit 2-D transpose, the four pixel
    phases interleaved, the rows' pad cut and ONE bitcast — the order
    a first conv reads (FrameRingReplay._gather says how it was
    arrived at; replay/sequence.py::_stacks is the other caller)."""
    px = [p.T for p in byte_transpose(w)]       # pixel 4k+i: px[i][k]
    px = jnp.stack(px, axis=1).reshape(-1, px[0].shape[-1])
    return jax.lax.bitcast_convert_type(px[:pixels], jnp.uint8)


def frame_mode(storage: str, obs_shape: tuple[int, ...]) -> bool:
    """THE single-frame-storage predicate — shared (aliased) by
    replay/frame_ring.frame_ring_mode (flat-DQN segment layout) and
    replay/sequence.sequence_frame_mode (R2D2 sequence layout), and
    through them by runtime/family.py (layout selection) and
    utils/hbm.py (budget pricing), so the selection and the pricing can
    never drift: frame mode applies to [H, W, stack] pixel observations
    under frame_ring storage, any dtype (the word-row packing inside
    the replay additionally engages only for uint8, but the item SHAPE
    is the same either way; the frame-ring layout's uint8 requirement
    is enforced with a ValueError at FrameRingReplay construction).

    Defined here rather than in either layout module because packing
    is the one module both already import — the two predicates used to
    be byte-identical copies, each claiming to be "THE predicate", and
    could drift exactly the way the claim promised they couldn't."""
    return storage == "frame_ring" and len(obs_shape) == 3


def ring_write_start(pos: jax.Array, block: int, capacity: int) -> jax.Array:
    """Start slot for an in-place contiguous ring write (skip-to-head).

    pos is the ring cursor; a `block`-slot write that would cross the
    ring boundary restarts at 0 (see module docstring). Returns the
    int32 start slot; the caller advances the cursor to
    (start + block) % capacity.

    Correct for ANY block size, including the non-dividing remainder a
    single-chip shutdown flush ships: tail slots a skip leaves behind
    keep their previous (still index-consistent) items, and callers
    must grow `size` as min(max(size, start + block), capacity) —
    NOT size + block — so never-written tail slots are never counted
    as filled (ring_write_size below).
    """
    assert block <= capacity, (block, capacity)
    return jnp.where(pos + block <= capacity, pos, 0).astype(jnp.int32)


def ring_write_size(size: jax.Array, start: jax.Array, block: int,
                    capacity: int) -> jax.Array:
    """Filled-slot count after a skip-to-head ring write. Pre-fill the
    ring fills [0, size) contiguously, so the new high-water mark is
    max(size, start + block); a skip that restarts at 0 therefore does
    NOT count the unwritten tail as filled (a plain size+block would —
    and uniform sampling would then draw all-zero slots)."""
    return jnp.minimum(jnp.maximum(size, start + block), capacity)


def dus_rows(buf: jax.Array, block: jax.Array, start: jax.Array,
             lead: int = 0) -> jax.Array:
    """dynamic_update_slice of a block at row `start` on axis `lead` —
    the in-place ring write (donated callers alias; scatter would
    copy). Axes before `lead` are written at origin over their full
    extent: the dist learners' lockstep form updates every [dp] shard
    of a [dp, capacity, ...] buffer in the same DUS (lead=1), which is
    what keeps the mesh add in place — a jax.vmap over the shard axis
    would rebatch the DUS into a full-copy scatter."""
    idx = ((jnp.int32(0),) * lead + (start,)
           + (jnp.int32(0),) * (buf.ndim - lead - 1))
    return jax.lax.dynamic_update_slice(buf, block.astype(buf.dtype), idx)


def dus_rows_per_shard(buf: jax.Array, block: jax.Array,
                       starts: jax.Array) -> jax.Array:
    """Per-shard directed ring write: shard d of a [dp, capacity, ...]
    buffer gets block[d] at row starts[d] — the dist form of the cold
    tier's add_at, where each shard's evict_plan picked its OWN region.

    dp single-shard multi-axis DUS calls, unrolled (dp is static).
    Chained DUS into a donated buffer alias in place; the obvious
    jax.vmap over the shard axis would rebatch the DUS into a
    lax.scatter and materialize a full-buffer copy (see dus_rows)."""
    dp = block.shape[0]
    out = buf
    for d in range(dp):
        idx = ((jnp.int32(d), starts[d])
               + (jnp.int32(0),) * (buf.ndim - 2))
        out = jax.lax.dynamic_update_slice(
            out, block[d:d + 1].astype(buf.dtype), idx)
    return out


def packable(spec) -> bool:
    """Pack uint8 pixel leaves big enough that tile padding matters.

    Small leaves (scalars, action vectors) stay in their natural layout
    — their padding is bytes, and reshaping them would cost more in
    decode than it saves.
    """
    return (np.dtype(spec.dtype) == np.uint8 and len(spec.shape) >= 2
            and math.prod(spec.shape) >= 4096)


# Widest byte row one storage gather fetches whole. Past it XLA:TPU
# splits the gather by COLUMNS ("mini-gather-slice": 255 lane tiles =
# 32,640 B each) and materializes every column slab of the whole
# operand: an R2D2 sequence stored as one 585,728 B row compiled to 18
# slabs of [capacity, 32640] — a copy of the entire 9 GiB ring inside
# every sample, 7.2 GiB of HLO temp, out of memory on the v5e (PERF.md
# §6, PR 26). Measured on byte rows; a row of words of the same bytes
# is a quarter of the lane tiles, so for word rows the bound is on the
# safe side (not measured again).
GATHER_ROW_MAX_BYTES = 255 * LANE


def row_layout(shape: tuple[int, ...]) -> tuple[int, int, int]:
    """-> (rows per item, bytes per row, padded bytes per row) of a
    packable uint8 leaf: ONE row per item while the padded row stays
    under GATHER_ROW_MAX_BYTES, else one row per slice of the leading
    axis (a frame of a sequence's [L + stack - 1, H, W], a stack of
    [L, H, W, stack]) — the frame ring's own row shape. A row is padded
    to whole lane tiles of words (`pad_row`). The one place the rule
    lives: PixelPacker lays storage out by it and utils/hbm.py prices
    by it."""
    nbytes = math.prod(shape)
    if pad_row(nbytes) <= GATHER_ROW_MAX_BYTES:
        return 1, nbytes, pad_row(nbytes)
    row = nbytes // shape[0]
    if pad_row(row) > GATHER_ROW_MAX_BYTES:
        raise ValueError(
            f"uint8 leaf {shape}: one slice of its leading axis is "
            f"{row} B, wider than the {GATHER_ROW_MAX_BYTES} B a "
            f"storage gather fetches whole")
    return shape[0], row, pad_row(row)


# What a sample hands on beside a split leaf `k`: the word rows it was
# gathered as, under `k + WORDS` (PixelPacker.decode).
WORDS = "_words"


class PixelPacker:
    """Per-leaf codec: pixel frames <-> exactly-tiled rows of words.

    Built from an item spec (pytree of ShapeDtypeStruct for ONE item).
    A packable leaf is stored as `rows` rows of pad_row bytes each, as
    uint32 words (`row_layout`: one row per item, or one per
    leading-axis slice when the item is wider than a gather fetches
    whole), ALL items' rows in one 2-D [capacity * rows, row // 4]
    buffer — a third dimension would tile-pad `rows` to a multiple of
    8. `storage_spec` is the shape of ONE row and `rows_per_item` the
    multiplicity; `encode` turns an incoming [*lead, b, ...] block of
    bytes into [*lead, b * rows, row // 4] words inside the add jit;
    `decode` restores sampled [*lead, rows, row // 4] rows (or
    [*lead, row // 4] for one-row leaves) to the original uint8 frame
    shape (touches only the batch).
    """

    def __init__(self, item_spec: Any):
        leaves, treedef = jax.tree.flatten(item_spec)
        self._treedef = treedef
        # per leaf: None | (orig_shape, rows, row_bytes, padded_row)
        self._plan = [(tuple(leaf.shape), *row_layout(tuple(leaf.shape)))
                      if packable(leaf) else None for leaf in leaves]

    @property
    def packs_anything(self) -> bool:
        return any(p is not None for p in self._plan)

    def storage_spec(self, item_spec: Any) -> Any:
        leaves = jax.tree.leaves(item_spec)
        out = [leaf if plan is None
               else jax.ShapeDtypeStruct((plan[3] // WORD,), jnp.uint32)
               for leaf, plan in zip(leaves, self._plan)]
        return jax.tree.unflatten(self._treedef, out)

    def rows_per_item(self) -> Any:
        """Pytree of ints: storage rows one item occupies, per leaf."""
        return jax.tree.unflatten(
            self._treedef, [1 if plan is None else plan[1]
                            for plan in self._plan])

    def encode(self, items: Any) -> Any:
        """[*lead, b, *orig] uint8 leaves -> [*lead, b * rows, row // 4]
        word rows (zero pad). Any number of leading axes before the
        block axis b ([b] single-chip, [dp, b] on the mesh)."""
        leaves = jax.tree.leaves(items)
        out = []
        for leaf, plan in zip(leaves, self._plan):
            if plan is None:
                out.append(leaf)
                continue
            shape, rows, nbytes, row = plan
            lead = leaf.shape[:leaf.ndim - len(shape)]
            flat = leaf.astype(jnp.uint8).reshape(
                *lead[:-1], lead[-1] * rows, nbytes)
            if row != nbytes:
                pad = [(0, 0)] * len(lead) + [(0, row - nbytes)]
                flat = jnp.pad(flat, pad)
            # dus_rows' own astype converts VALUES and would store one
            # pixel per word: pack here
            out.append(as_words(flat, strided=True))
        return jax.tree.unflatten(self._treedef, out)

    def decode(self, items: Any, words: bool = False) -> Any:
        """Sampled word rows -> [*lead, *orig] uint8 frames: [*lead,
        row // 4] for a one-row leaf, [*lead, rows, row // 4] for a
        split one.

        `words` (a dict of items; what a SAMPLE hands on): a split leaf
        `k` also goes on as the rows it was gathered as, under
        `k + WORDS`, for a reader that works on whole words
        (replay/sequence.py::_stacks builds conv1's operand from them
        and takes only the frame shape from the bytes). Inside a jit
        whichever of the two forms nobody reads is never computed."""
        leaves = jax.tree.leaves(items)
        out = []
        for leaf, plan in zip(leaves, self._plan):
            if plan is None:
                out.append(leaf)
                continue
            shape, rows, nbytes, _ = plan
            lead = leaf.shape[:-1] if rows == 1 else leaf.shape[:-2]
            out.append(as_bytes(leaf)[..., :nbytes].reshape(*lead, *shape))
        frames = jax.tree.unflatten(self._treedef, out)
        if not words:
            return frames
        return {**frames, **{k + WORDS: items[k] for k, m
                             in self.rows_per_item().items() if m > 1}}

    def checkpoint_rows(self, storage: Any, restore: bool = False) -> Any:
        """HOST storage (numpy leaves) <-> what a replay-bearing
        checkpoint holds of it (utils/checkpoint.py): a packed leaf as
        the BYTE rows these stores kept before their rows were words,
        uint8 [..., R, pad128(row bytes)] (layout v2/v3), so a file on
        disk means what it meant. A word's bytes lie least significant
        first, as a little-endian view of the rows reads them;
        `restore` goes back to [..., R, row // 4] uint32."""
        leaves = jax.tree.leaves(storage)
        out = []
        for leaf, plan in zip(leaves, self._plan):
            if plan is None:
                out.append(leaf)
                continue
            _, _, nbytes, row = plan
            if restore:
                if leaf.shape[-1] != row:
                    leaf = np.pad(leaf, [(0, 0)] * (leaf.ndim - 1)
                                  + [(0, row - leaf.shape[-1])])
                out.append(np.ascontiguousarray(leaf).view("<u4"))
            else:
                out.append(np.ascontiguousarray(
                    leaf.astype("<u4", copy=False).view(np.uint8)
                    [..., :pad128(nbytes)]))
        return jax.tree.unflatten(self._treedef, out)


# -- cold-segment serialization (replay/cold_store.py) ----------------------
#
# A cold segment is one eviction region — `n` staging units of the item
# spec plus their stored priorities — flattened to ONE host byte string:
# per leaf, delta-XOR (uint8 pixel leaves, reusing the wire codec's
# kernels in comm/native.py) + zlib deflate, framed with pack_records.
# A 1-byte mode prefix per leaf records what was applied, with a
# per-leaf never-inflate guard: if deflate would grow a leaf, its raw
# bytes are stored instead (mode 0), so a segment's payload can exceed
# its raw bytes only by the constant framing overhead (9 bytes/leaf).
# Round trips are bitwise exact in every mode (XOR and deflate both
# are; tests/test_cold_store.py pins it on both storage layouts).

_COLD_RAW = 0        # leaf bytes verbatim
_COLD_DEFLATE = 1    # zlib only
_COLD_DELTA = 2      # XOR-delta rows, then zlib


def cold_plan(item_spec: Any, ptail: tuple = ()) -> list[tuple]:
    """Per-leaf serialization plan for one staging unit: [(key, shape,
    dtype, delta_rows)]. delta_rows is the per-unit leading axis the
    XOR-delta transform rows over (frames of a segment / image rows of
    a stacked obs) or 0 for non-delta leaves. "priorities" (trailing
    shape `ptail`, f32) is appended — it rides every cold segment so a
    recall can restage with its eviction-time priority mass."""
    plan = []
    entries = [(k, tuple(s.shape), np.dtype(s.dtype))
               for k, s in item_spec.items()]
    entries.append(("priorities", tuple(ptail), np.dtype(np.float32)))
    for key, shape, dtype in entries:
        unit_bytes = math.prod(shape) * dtype.itemsize if shape \
            else dtype.itemsize
        delta_rows = (int(shape[0])
                      if (dtype == np.uint8 and len(shape) >= 2
                          and unit_bytes >= 4096) else 0)
        plan.append((key, shape, dtype, delta_rows))
    return plan


def cold_pack(items: dict, plan: list[tuple],
              level: int = 1) -> tuple[bytes, int]:
    """Serialize {key: [n, *shape] host arrays} -> (payload, raw_bytes)
    following `plan`. Pure host work (numpy + zlib + the comm/native.py
    delta kernels or their bit-identical numpy fallback)."""
    import zlib

    from ape_x_dqn_tpu.comm.native import delta_encode, pack_records

    chunks = []
    raw_total = 0
    for key, shape, dtype, delta_rows in plan:
        a = np.ascontiguousarray(np.asarray(items[key], dtype=dtype))
        raw_total += a.nbytes
        if delta_rows:
            n = a.shape[0]
            body = zlib.compress(
                delta_encode(a.reshape(n * delta_rows, -1)), level)
            mode = _COLD_DELTA
        else:
            body = zlib.compress(a.tobytes(), level)
            mode = _COLD_DEFLATE
        if len(body) >= a.nbytes:  # never-inflate guard (per leaf)
            body, mode = a.tobytes(), _COLD_RAW
        chunks.append(bytes([mode]) + body)
    return pack_records(chunks), raw_total


def cold_unpack(payload: bytes, plan: list[tuple], n: int) -> dict:
    """Inverse of cold_pack: payload -> {key: [n, *shape] arrays},
    bitwise equal to what went in. Returned arrays may be read-only
    views over decompressed bytes (the restage path only reads)."""
    import zlib

    from ape_x_dqn_tpu.comm.native import (delta_undo_inplace,
                                           unpack_records)

    recs = unpack_records(payload, max_records=len(plan) + 1)
    if len(recs) != len(plan):
        raise ValueError(
            f"cold segment holds {len(recs)} leaves, plan expects "
            f"{len(plan)} — segment written under a different item spec")
    out = {}
    for (key, shape, dtype, delta_rows), rec in zip(plan, recs):
        mode, body = rec[0], rec[1:]
        if mode == _COLD_RAW:
            raw: Any = body
        elif mode == _COLD_DEFLATE:
            raw = zlib.decompress(body)
        elif mode == _COLD_DELTA:
            rows = np.frombuffer(zlib.decompress(body), np.uint8) \
                .reshape(n * delta_rows, -1).copy()
            delta_undo_inplace(rows)
            raw = rows
        else:
            raise ValueError(f"unknown cold leaf mode {mode}")
        buf = raw.tobytes() if isinstance(raw, np.ndarray) else raw
        out[key] = np.frombuffer(buf, dtype=dtype).reshape((n, *shape))
    return out


def make_packer(item_spec: Any) -> tuple[PixelPacker | None, Any, Any]:
    """-> (packer or None, storage spec of ONE row, rows per item):
    the one place the packing decision is made, shared by every replay
    class so storage layout and the HBM budget (utils/hbm.py) cannot
    drift. A leaf's buffer is [capacity * rows, *row spec]."""
    spec = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), item_spec)
    packer = PixelPacker(spec)
    if packer.packs_anything:
        return packer, packer.storage_spec(spec), packer.rows_per_item()
    return None, spec, jax.tree.map(lambda _: 1, spec)


def gather_rows(buf: jax.Array, idx: jax.Array, rows: int) -> jax.Array:
    """Item gather from a [capacity * rows, ...] buffer: [n, ...] for a
    one-row leaf, [n, rows, ...] for a split one (item i is the `rows`
    consecutive rows from i * rows). The row position is FIRST in the
    index, as in the frame ring's gather, so the n rows of one position
    arrive together and the result is [rows, n, ...] seen item-major: a
    reader that slices positions (replay/sequence.py::_stacks) then
    takes whole blocks, and no [n * rows] -> [n, rows] reshape pads
    `rows` to the tile (r2d2_offline: 6.64 -> 6.31 ms a step on the
    v5e, PERF.md §6, PR 42)."""
    if rows == 1:
        return buf[idx]
    offs = jnp.arange(rows, dtype=idx.dtype)[:, None]
    return buf[offs + idx[None, :] * rows].swapaxes(0, 1)
