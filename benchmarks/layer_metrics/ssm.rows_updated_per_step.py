"""Rows whose Mamba state a decode step advanced, a step: the program's
counter `ssm_rows_updated` (valid rows x Mamba layers a dispatch, summed
inside the device program; padding rows are not counted) / the Mamba
layers / the batches of the window. A gauge the run decides: how full
the server's steps were. None without the counter."""

from benchmarks.harness import flops_jamba as counts


def read(facts: dict) -> float | None:
    c = facts.get("slot_counters") or {}
    model_sizes = counts.model_sizes(facts)
    batches = (facts.get("server_window") or {}).get("batches")
    if not c.get("ssm_rows_updated") or not model_sizes or not batches:
        return None
    mamba = list(model_sizes["layer_kinds"]).count(counts.MAMBA)
    return c["ssm_rows_updated"] / mamba / batches
