"""Mean of the program span `server.stack`: per batch, the lead dims,
`_pad_concat` of the requests' observations and the sharded
`device_put` (`BatchedInferenceServer._serve_batch`). Obs on only."""

from benchmarks.harness.span_stats import mean_ms


def read(facts: dict) -> float | None:
    return mean_ms(facts, "server.stack")
