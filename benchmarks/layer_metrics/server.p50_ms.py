"""Client-side median of `query_batch` latency over the window's
queries, send to reply."""


def read(facts: dict) -> float | None:
    return (facts.get("query_latency_ms") or {}).get("p50")
