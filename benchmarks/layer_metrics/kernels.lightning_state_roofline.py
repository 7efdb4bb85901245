"""The lightning recurrence's share of its HBM roofline in a decode
step, in %: every row's float32 matrices read and written once a layer
(harness/flops_minicpm_sala.lightning_bytes) / the table's HBM
bandwidth / the device time a step spends under `sala.lightning.state`
and the slot gather and scatter around it (`slots.read`, `slots.write`:
the matrices' only other touch; the sparse layers' key writes under
`slots.write` are in the time and not in the bytes, so the share reads
low, never high)."""

from benchmarks.harness import flops_minicpm_sala as counts
from benchmarks.harness import sala_scopes
from benchmarks.harness.peaks import peaks_for


def read(facts: dict) -> float | None:
    decode = facts.get("decode")
    model_sizes = facts["runtime"].cell.config.get("model_sizes") or {}
    if not decode or "mixer_types" not in model_sizes:
        return None
    seconds = sala_scopes.seconds_per_step(
        facts, "sala.lightning.state", "slots.read", "slots.write")
    if not seconds:
        return None
    peak = peaks_for(facts["runtime"].devices[0].device_kind)
    floor = (counts.lightning_bytes(model_sizes, decode["rows_per_step"])
             / peak.hbm_bytes_per_s)
    return 100.0 * floor / seconds
