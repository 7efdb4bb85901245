"""Mean of the program span `server.collect`: per served batch, the
serve thread's wait for the first request plus the 2 ms fill
(`BatchedInferenceServer._collect_traced`; empty polls are not
recorded). Obs on only."""

from benchmarks.harness.span_stats import mean_ms


def read(facts: dict) -> float | None:
    return mean_ms(facts, "server.collect")
