"""Share of the device's busy time under the scope `sala.sparse` (a
sparse layer's mixer whole: projections, the slot writes, compressed
keys, selection, attention, output), in %. None where no op carries
the scope."""

from benchmarks.harness import sala_scopes


def read(facts: dict) -> float | None:
    return sala_scopes.share_of_busy(facts, "sala.sparse")
