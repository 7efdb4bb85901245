"""The frame ring's sample gather as a share of its roofline, in %:
bytes the gather has to move per grad step on one chip / device time
under the scope `replay.sample_gather` (FrameRingReplay._gather: the
row fetch alone, not the relayout behind it) per grad step / the
table's HBM peak. The gather computes nothing, so the roofline is
bytes over peak bytes/s.

Bytes, from the configuration file's shapes (`gather_bytes_per_step`):
the pixels of the chip's share of the batch, `stack` frames of H x W
bytes for `obs` and again for `next_obs`, counted once - no row
padding, and not the neighbouring rows a packed layout drags along;
the write of what was read is left out as well, so a perfect gather
reads 50%. Time: the scope's share of busy time x the device time of
a grad step (`learner.step_ms`'s own reader), first chip, so both come
from the one trace. A program without the scope (the parent of the PR
that opened it) or a configuration without a frame ring reads
nothing."""

from benchmarks.harness import cells, scope_stats
from benchmarks.harness.peaks import peaks_for

SCOPE = "replay.sample_gather"


def gather_bytes_per_step(batch_per_chip: int, frame: list) -> int:
    height, width, stack = frame
    return 2 * batch_per_chip * stack * height * width


def read(facts: dict) -> float | None:
    runtime = facts["runtime"]
    sizes = runtime.cell.config.get("sizes", {})
    if sizes.get("replay_storage") != "frame_ring":
        return None
    busy_ns = facts["trace"]["devices"][0]["busy_ns"]
    gather_ns = scope_stats.of(facts).get(SCOPE)
    step_ms = cells.layer_metric_reader("learner.step_ms").read(facts)
    if not gather_ns or not step_ms or not busy_ns:
        return None
    gather_s_per_step = gather_ns / busy_ns * step_ms / 1e3
    achieved = gather_bytes_per_step(
        facts["batch_size"] // runtime.cell.chips,
        sizes["frame"]) / gather_s_per_step
    peak = peaks_for(runtime.devices[0].device_kind)
    return 100.0 * achieved / peak.hbm_bytes_per_s
