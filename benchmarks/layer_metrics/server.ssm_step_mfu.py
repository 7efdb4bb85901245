"""Model FLOP/s utilisation of a slot server's decode steps over the
window for the family `jamba_slots`, in %: the FLOP one step's answers
need (harness/flops_jamba.step_flops, from the configuration's
`model_sizes` and the window's rows and contexts) x steps / s (the
server's batches over the host's window) / the table's bf16 peak. The
whole step's bounded share: a decode step reads every weight for a
hundred rows and walks a recurrence on the VPU, so it reads low by
nature; it says nothing about idle time. None without the kind's
`decode` facts or this family's `model_sizes`."""

from benchmarks.harness import flops_jamba as counts
from benchmarks.harness.peaks import peaks_for


def read(facts: dict) -> float | None:
    contexts = counts.window_contexts(facts)
    model_sizes = counts.model_sizes(facts)
    if not contexts or not model_sizes:
        return None
    steps = facts["server_window"]["batches"] / facts["window_s"]
    peak = peaks_for(facts["runtime"].devices[0].device_kind)
    return (100.0 * counts.step_flops(model_sizes, contexts) * steps
            / peak.bf16_flops_per_s)
