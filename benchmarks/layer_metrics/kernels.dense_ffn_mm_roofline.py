"""The dense MLP's share of the MXU's peak, in %: FLOP executed under
the scope `ouro.mlp` per grad step
(harness/flops_ouro.executed_dense_ffn_flops: 6 x rows x hidden x
intermediate per forward application, over prefix and trained segment,
both nets, the online net's recomputation and its backward pass, loop
steps x layers applications) / the device time per grad step under that
scope / the table's bf16 peak. That time is the scope's share of busy
time x `learner.step_ms`, both from the one trace. Compute-bound: at
3,072 rows the three matrices' 69 MB of bfloat16 are read once per
application for 212 GFLOP. The scope also holds the activation and the
casts of the float32 weights, so the share cannot pass what the MXU
did; a reading above 100% would mean the rows are counted too high or
the scope misses part of the work."""

from benchmarks.harness import cells, flops_ouro, ouro_scopes
from benchmarks.harness.peaks import peaks_for


def read(facts: dict) -> float | None:
    model_sizes = facts["runtime"].cell.config.get("model_sizes")
    if not model_sizes or "total_ut_steps" not in model_sizes:
        return None
    busy_ns = facts["trace"]["devices"][0]["busy_ns"]
    scope_ns = ouro_scopes.of(facts).get("ouro.mlp")
    step_ms = cells.layer_metric_reader("learner.step_ms").read(facts)
    if not scope_ns or not step_ms or not busy_ns:
        return None
    seconds_per_step = scope_ns / busy_ns * step_ms / 1e3
    flops = flops_ouro.executed_dense_ffn_flops(facts["batch_size"],
                                                model_sizes)
    peak = peaks_for(facts["runtime"].devices[0].device_kind)
    return 100.0 * flops / seconds_per_step / peak.bf16_flops_per_s
