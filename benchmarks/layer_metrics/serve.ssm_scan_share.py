"""Share of the device's busy time under the scope `jamba.mamba.scan`
(the selective scan with its state's gather from and scatter into the
slot rows: nothing that is a matmul), in %. None where no op carries the
scope."""

from benchmarks.harness import jamba_scopes


def read(facts: dict) -> float | None:
    return jamba_scopes.share_of_busy(facts, "jamba.mamba.scan")
