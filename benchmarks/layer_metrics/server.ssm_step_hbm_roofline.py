"""A decode step's share of its HBM roofline for the family
`jamba_slots`, in %: the bytes the step cannot avoid moving
(harness/flops_jamba.step_bytes: every matrix once, each row's Mamba
state and conv tails read and written, each row's keys and values read)
/ the table's HBM bandwidth / the step's device time
(`server.decode_step_ms`'s median). Bytes bound a decode step; a reading
above 100% would mean the bytes are counted too high."""

from benchmarks.harness import flops_jamba as counts
from benchmarks.harness import jamba_scopes
from benchmarks.harness.peaks import peaks_for


def read(facts: dict) -> float | None:
    contexts = counts.window_contexts(facts)
    model_sizes = counts.model_sizes(facts)
    if not contexts or not model_sizes:
        return None
    seconds = jamba_scopes.step_seconds(facts)
    if not seconds:
        return None
    peak = peaks_for(facts["runtime"].devices[0].device_kind)
    floor = counts.step_bytes(model_sizes, contexts) / peak.hbm_bytes_per_s
    return 100.0 * floor / seconds
