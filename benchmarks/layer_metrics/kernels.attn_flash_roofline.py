"""The blockwise attention's share of the MXU's peak, in %: FLOP it
executes per grad step (harness/flops_afmoe.executed_attention_flops:
4 x head_dim x heads per query-key pair the mask admits and forward
pass, over every pass of the step - prefix and trained segment, both
nets, the online net's recomputation and its backward pass of five tile
matmuls) / the device time per grad step under the scopes
`afmoe.attn.sliding` and `afmoe.attn.full` / the table's bf16 peak.
That time is the scopes' share of busy time x `learner.step_ms`, both
from the one trace. Pairs, not tiles: the masked part of the tiles on
the diagonal and on the window's edge is time without counted work, so
the share cannot pass what the MXU did. A reading above 100% would mean
the pairs are counted too high or the scopes miss part of the work."""

from benchmarks.harness import afmoe_scopes, cells, flops_afmoe
from benchmarks.harness.peaks import peaks_for


def read(facts: dict) -> float | None:
    model_sizes = facts["runtime"].cell.config.get("model_sizes")
    if not model_sizes or "layer_types" not in model_sizes:
        return None
    busy_ns = facts["trace"]["devices"][0]["busy_ns"]
    kernel_ns = afmoe_scopes.kernel_ns(facts)
    step_ms = cells.layer_metric_reader("learner.step_ms").read(facts)
    if not kernel_ns or not step_ms or not busy_ns:
        return None
    seconds_per_step = kernel_ns / busy_ns * step_ms / 1e3
    flops = flops_afmoe.executed_attention_flops(facts["batch_size"],
                                                 model_sizes)
    peak = peaks_for(facts["runtime"].devices[0].device_kind)
    return 100.0 * flops / seconds_per_step / peak.bf16_flops_per_s
