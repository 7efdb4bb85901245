"""The data-chosen attention's share of its HBM roofline in a decode
step, in %: the attended blocks' keys and values and the visible
compressed keys of every row and sparse layer read once
(harness/flops_minicpm_sala.sparse_bytes) / the table's HBM bandwidth /
the device time a step spends under `sala.sparse.select` +
`sala.sparse.attend`. Gathers run further under their floor than
matmuls do; a reading above 100% would mean the blocks are counted too
high or the scopes miss part of the work."""

from benchmarks.harness import flops_minicpm_sala as counts
from benchmarks.harness import sala_scopes
from benchmarks.harness.peaks import peaks_for


def read(facts: dict) -> float | None:
    contexts = counts.window_contexts(facts)
    model_sizes = facts["runtime"].cell.config.get("model_sizes") or {}
    if not contexts or "mixer_types" not in model_sizes:
        return None
    seconds = sala_scopes.seconds_per_step(
        facts, "sala.sparse.select", "sala.sparse.attend")
    if not seconds:
        return None
    peak = peaks_for(facts["runtime"].devices[0].device_kind)
    floor = counts.sparse_bytes(model_sizes, contexts) / peak.hbm_bytes_per_s
    return 100.0 * floor / seconds
