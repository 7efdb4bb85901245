"""The gated short convolution's share of its roofline, in %: the least
time the chip could take for THE WHOLE CONV OPERATOR per grad step
(harness/flops_lfm2.conv_floor_seconds: the two projections' and the
mixer's FLOP a token and pass, and the bytes of the operator's input
read and its output written once a pass in the dtype the configuration
states, over every pass of the step - prefix and trained segment, both
nets, the online net's recomputation and its backward pass -, the
LARGER of FLOP / the table's bf16 peak and bytes / its HBM bandwidth;
at hidden 2,048 the FLOP bound it) / the device time per grad step
under the scope `lfm2.conv`, which holds the projections, both gates
and the filter whichever fusion XLA puts the gates in. That time is the
scope's share of busy time x `learner.step_ms`, both from the one
trace. The work is counted from the configuration's `model_sizes`,
never from how the program fuses it, so the share reads the same work
whatever implements the operator and an operator fused into one kernel
cannot pass 100%: it still makes every product counted here. What the
share leaves under 100% is the projections' distance from the MXU's
peak and ALL of the gates' and filter's time (`learner.conv_mix_share`
has the part of it that kept the scope `lfm2.conv.mix`). A reading
above 100% would mean the passes are counted too high or the scope
misses part of the work."""

from benchmarks.harness import cells, flops_lfm2, lfm2_scopes
from benchmarks.harness.peaks import peaks_for


def read(facts: dict) -> float | None:
    config = facts["runtime"].cell.config
    model_sizes = config.get("model_sizes")
    if not model_sizes or "conv_L_cache" not in model_sizes:
        return None
    busy_ns = facts["trace"]["devices"][0]["busy_ns"]
    conv_ns = lfm2_scopes.of(facts).get("lfm2.conv")
    step_ms = cells.layer_metric_reader("learner.step_ms").read(facts)
    if not conv_ns or not step_ms or not busy_ns:
        return None
    seconds_per_step = conv_ns / busy_ns * step_ms / 1e3
    peak = peaks_for(facts["runtime"].devices[0].device_kind)
    sizes = {**config["sizes"], "batch_size": facts["batch_size"]}
    floor = flops_lfm2.conv_floor_seconds(sizes, model_sizes, peak)
    return 100.0 * floor / seconds_per_step
