"""Share of the device's busy time under the scope `jamba.mamba` (a
Mamba mixer whole: projections, conv, gates, the state's read, the scan,
its write, the output gate), in %. None where no op carries the scope."""

from benchmarks.harness import jamba_scopes


def read(facts: dict) -> float | None:
    return jamba_scopes.share_of_busy(facts, "jamba.mamba")
