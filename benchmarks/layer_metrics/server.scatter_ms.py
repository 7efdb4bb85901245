"""Mean of the program span `server.scatter`: per batch, slicing the
results per request, `event.set()` for each caller and the latency
histogram (`BatchedInferenceServer._serve_batch`). Obs on only."""

from benchmarks.harness.span_stats import mean_ms


def read(facts: dict) -> float | None:
    return mean_ms(facts, "server.scatter")
