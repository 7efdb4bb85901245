"""The grouped expert matmuls' share of the MXU's peak, in %: FLOP the
program executes in them per grad step
(harness/flops_glm_moe.executed_expert_flops: 6 x rows routed here x
hidden x expert width per forward pass, the rows from the step's own
counters, the online net's trained rows counted four times — forward,
recomputed forward, backward of two) / the kernels' device time per
grad step / the table's bf16 peak. The kernels are what XLA:TPU makes
of `jax.lax.ragged_dot`: device ops named `ragged-dot-...`, category
custom-call, which carry no name stack, so they are found by name
(harness/glm_scopes.grouped_matmul_ns) and not under the scope
`glm.moe.experts`, which holds only the elementwise work around them.
Their time per step is their share of busy time x `learner.step_ms`,
both from the one trace. A kernel skips the tiles past its last group,
so its time follows the rows. A reading above 100% would mean the rows
are counted too high or the name misses part of the work."""

from benchmarks.harness import cells, flops_glm_moe, glm_scopes
from benchmarks.harness.peaks import peaks_for


def read(facts: dict) -> float | None:
    moe = facts.get("moe")
    model_sizes = facts["runtime"].cell.config.get("model_sizes")
    if not moe or not model_sizes:
        return None
    busy_ns = facts["trace"]["devices"][0]["busy_ns"]
    kernel_ns = glm_scopes.grouped_matmul_ns(facts)
    step_ms = cells.layer_metric_reader("learner.step_ms").read(facts)
    if not kernel_ns or not step_ms or not busy_ns:
        return None
    seconds_per_step = kernel_ns / busy_ns * step_ms / 1e3
    flops = flops_glm_moe.executed_expert_flops(
        moe["rows_per_step"], moe["rows_grad_per_step"], model_sizes)
    peak = peaks_for(facts["runtime"].devices[0].device_kind)
    return 100.0 * flops / seconds_per_step / peak.bf16_flops_per_s
