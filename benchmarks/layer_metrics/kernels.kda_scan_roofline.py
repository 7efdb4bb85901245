"""The delta rule's share of its roofline, in %: the least time the chip
could take for the recurrence's own work per grad step
(harness/flops_kimi_linear.scan_floor_seconds: 7 d_k d_v FLOP a token,
head and pass and the float32 bytes of q, k, v, g, beta read and o
written once a pass, over every pass of the step - prefix and trained
segment, both nets, the online net's recomputation and its backward
pass with the cotangents -, the LARGER of FLOP / the table's bf16 peak
and bytes / its HBM bandwidth; at heads of 128 the bytes bound it) /
the device time per grad step under the scope `kda.scan`. That time is
the scope's share of busy time x `learner.step_ms`, both from the one
trace. The work is counted from the configuration's `model_sizes`,
never from the chunk size, so the share reads the same work whatever
implements the scan. A reading above 100% would mean the passes are
counted too high or the scope misses part of the work."""

from benchmarks.harness import cells, flops_kimi_linear, kda_scopes
from benchmarks.harness.peaks import peaks_for


def read(facts: dict) -> float | None:
    model_sizes = facts["runtime"].cell.config.get("model_sizes")
    if not model_sizes or "mixer_types" not in model_sizes:
        return None
    busy_ns = facts["trace"]["devices"][0]["busy_ns"]
    scan_ns = kda_scopes.of(facts).get("kda.scan")
    step_ms = cells.layer_metric_reader("learner.step_ms").read(facts)
    if not scan_ns or not step_ms or not busy_ns:
        return None
    seconds_per_step = scan_ns / busy_ns * step_ms / 1e3
    peak = peaks_for(facts["runtime"].devices[0].device_kind)
    floor = flops_kimi_linear.scan_floor_seconds(
        facts["batch_size"], model_sizes, peak)
    return 100.0 * floor / seconds_per_step
