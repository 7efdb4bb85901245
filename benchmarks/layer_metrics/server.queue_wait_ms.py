"""Mean wait of one request from `query_batch`'s enqueue to the serve
thread taking it into a batch: program span `server.queue_wait`, one
interval per request (`BatchedInferenceServer._collect_traced`), total
/ count. Obs on only."""

from benchmarks.harness.span_stats import mean_ms


def read(facts: dict) -> float | None:
    return mean_ms(facts, "server.queue_wait")
