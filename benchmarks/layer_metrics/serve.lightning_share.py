"""Share of the device's busy time under the scope `sala.lightning` (a
lightning layer's mixer whole: projections, the slot read, the
recurrence, the slot write, output norm and gate), in %. None where no
op carries the scope."""

from benchmarks.harness import sala_scopes


def read(facts: dict) -> float | None:
    return sala_scopes.share_of_busy(facts, "sala.lightning")
