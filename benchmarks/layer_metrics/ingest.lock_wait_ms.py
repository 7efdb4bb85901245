"""Mean of the program span `state_lock.wait.ingest`: per ship, the
ingest thread's wait from asking for `ApexDriver._state_lock` to
holding it (`_ship_staged`). Obs on only."""

from benchmarks.harness.span_stats import mean_ms


def read(facts: dict) -> float | None:
    return mean_ms(facts, "state_lock.wait.ingest")
