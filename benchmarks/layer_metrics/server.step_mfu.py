"""Model FLOP/s utilisation of a slot server's decode steps over the
window, in %: the FLOP one step's answers need
(harness/flops_minicpm_sala.step_flops, from the configuration's
`model_sizes` and the sessions' contexts at the window's middle) x
steps / s (the server's batches over the host's window) / the table's
bf16 peak. The whole step's bounded share: a decode step reads every
weight for a few dozen rows, so it reads low by nature; it says nothing
about idle time. None without the kind's `decode` facts."""

from benchmarks.harness import flops_minicpm_sala as counts
from benchmarks.harness.peaks import peaks_for


def read(facts: dict) -> float | None:
    contexts = counts.window_contexts(facts)
    model_sizes = facts["runtime"].cell.config.get("model_sizes") or {}
    if not contexts or "mixer_types" not in model_sizes:
        return None
    steps = facts["server_window"]["batches"] / facts["window_s"]
    peak = peaks_for(facts["runtime"].devices[0].device_kind)
    return (100.0 * counts.step_flops(model_sizes, contexts) * steps
            / peak.bf16_flops_per_s)
