"""The residual of the cycle's account: 100 - the shares of device
busy time under the seven `cycle.*` scopes (runtime/learner.py::
CYCLE_SCOPES), in %, first chip - the train loop's own `while` time,
the rng split, copies XLA inserts without metadata, a fusion that took
another root's name. Nothing (not 100) where the trace holds none of
the seven. benchmarks/harness/cycle_scopes.py says how the account is
read."""

from benchmarks.harness import cycle_scopes


def read(facts: dict) -> float | None:
    return cycle_scopes.share_of_busy(facts, cycle_scopes.UNSCOPED)
