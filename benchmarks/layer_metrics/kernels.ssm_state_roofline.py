"""The selective scan's share of its HBM roofline in a decode step, in
%: every row's Mamba state and conv tail read and written once a layer
(harness/flops_jamba.ssm_state_bytes) / the table's HBM bandwidth / the
device time a step spends under `jamba.mamba.scan` (their gather from
the slot rows, the update, their scatter). None where no op carries the
scope."""

from benchmarks.harness import flops_jamba as counts
from benchmarks.harness import jamba_scopes
from benchmarks.harness.peaks import peaks_for


def read(facts: dict) -> float | None:
    decode = facts.get("decode")
    model_sizes = counts.model_sizes(facts)
    if not decode or not model_sizes:
        return None
    seconds = jamba_scopes.seconds_per_step(facts, "jamba.mamba.scan")
    if not seconds:
        return None
    peak = peaks_for(facts["runtime"].devices[0].device_kind)
    floor = (counts.ssm_state_bytes(model_sizes, decode["rows_per_step"])
             / peak.hbm_bytes_per_s)
    return 100.0 * floor / seconds
