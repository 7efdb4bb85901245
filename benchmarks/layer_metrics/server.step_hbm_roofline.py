"""A decode step's share of its HBM roofline, in %: the bytes the step
cannot avoid moving (harness/flops_minicpm_sala.step_bytes: every
matrix but the embedding once, each row's lightning matrices read and
written, each row's attended blocks and visible compressed keys) / the
table's HBM bandwidth / the step's device time
(`server.decode_step_ms`'s median). Bytes bound a decode step; a
reading above 100% would mean the bytes are counted too high."""

from benchmarks.harness import flops_minicpm_sala as counts
from benchmarks.harness import sala_scopes
from benchmarks.harness.peaks import peaks_for


def read(facts: dict) -> float | None:
    contexts = counts.window_contexts(facts)
    model_sizes = facts["runtime"].cell.config.get("model_sizes") or {}
    if not contexts or "mixer_types" not in model_sizes:
        return None
    seconds = sala_scopes.step_seconds(facts)
    if not seconds:
        return None
    peak = peaks_for(facts["runtime"].devices[0].device_kind)
    floor = counts.step_bytes(model_sizes, contexts) / peak.hbm_bytes_per_s
    return 100.0 * floor / seconds
