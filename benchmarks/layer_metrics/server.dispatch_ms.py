"""Mean of the program span `server.dispatch`: per batch, the params
read and `self._apply(params, stacked)` — the host-to-device copy of the
batch and the enqueue (`BatchedInferenceServer._serve_batch`). Obs on
only."""

from benchmarks.harness.span_stats import mean_ms


def read(facts: dict) -> float | None:
    return mean_ms(facts, "server.dispatch")
