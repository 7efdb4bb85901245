"""The sequence gather's share of its roofline, in %: bytes the gather
has to move per grad step / device time under the scope
`replay.sample_gather` (PrioritizedReplay.sample_items) per grad step /
the table's HBM peak. The gather is bound by bandwidth (it computes
nothing), so the roofline is bytes over peak bytes/s.

Bytes, from the configuration file's shapes (`item_bytes`,
`gather_bytes_per_step`): the useful bytes of the batch's items - the
single frames, the four per-step fields, the stored state; no padding
of whatever layout the program stores them in - read once and written
once. Time: the scope's share of busy time x the device time of a grad
step (`learner.step_ms`'s own reader), so both come from the one trace. A
reading above 100% would mean the bytes are counted too high."""

from benchmarks.harness import cells, scope_stats
from benchmarks.harness.peaks import peaks_for

SCOPE = "replay.sample_gather"


def item_bytes(sizes: dict, sequence_sizes: dict) -> int:
    """One frame-mode sequence item: L + stack - 1 single uint8 frames,
    actions (int32), rewards, terminals and mask (float32) per step,
    and the stored (c, h) in float32."""
    height, width, stack = sizes["frame"]
    length = sequence_sizes["seq_length"]
    return ((length + stack - 1) * height * width + 4 * length * 4
            + 2 * sequence_sizes["lstm_size"] * 4)


def gather_bytes_per_step(batch_size: int, one_item_bytes: int) -> int:
    """One grad step trains on `batch_size` items; the K-batch draw
    gathers K of those batches at once, K times less often."""
    return 2 * batch_size * one_item_bytes


def read(facts: dict) -> float | None:
    config = facts["runtime"].cell.config
    if "sequence_sizes" not in config:
        return None
    one_item_bytes = item_bytes(config["sizes"], config["sequence_sizes"])
    busy_ns = facts["trace"]["devices"][0]["busy_ns"]
    gather_ns = scope_stats.of(facts).get(SCOPE)
    step_ms = cells.layer_metric_reader("learner.step_ms").read(facts)
    if not gather_ns or not step_ms or not busy_ns:
        return None
    gather_s_per_step = gather_ns / busy_ns * step_ms / 1e3
    achieved = gather_bytes_per_step(
        facts["batch_size"], one_item_bytes) / gather_s_per_step
    peak = peaks_for(facts["runtime"].devices[0].device_kind)
    return 100.0 * achieved / peak.hbm_bytes_per_s
