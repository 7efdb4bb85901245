"""End-to-end model FLOP/s utilisation of the window, in %: analytic
FLOP per step (benchmarks/harness/flops.py, by the configuration's
`family`) x grad steps / s / (chips x the table's bf16 peak). A
utilisation of the whole step — not a kernel's roofline share, and it
says nothing about idle time."""

from benchmarks.harness.flops import TRAIN_STEP_FLOPS
from benchmarks.harness.peaks import peaks_for


def read(facts: dict) -> float | None:
    if not facts.get("grad_steps"):
        return None
    rt = facts["runtime"]
    flops = TRAIN_STEP_FLOPS[facts["family"]](rt.sizes)
    peak = peaks_for(rt.devices[0].device_kind).bf16_flops_per_s
    rate = facts["grad_steps"] / facts["window_s"]
    return 100.0 * flops * rate / (len(rt.devices) * peak)
