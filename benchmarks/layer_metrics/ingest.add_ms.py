"""Mean of the program's own `replay.add` span (host time of one add /
add_many dispatch under the state lock), from `obs.trace.SpanTracer`
aggregates. The span exists only with obs on, which the traced run
turns on; with obs off there is nothing to read."""


def read(facts: dict) -> float | None:
    span = (facts.get("program_spans") or {}).get("replay.add")
    if not span or not span.get("count"):
        return None
    return span["total_ms"] / span["count"]
