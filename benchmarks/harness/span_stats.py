"""The program's own spans as the traffic kind hands them over:
`facts["program_spans"]` maps a span name of `obs.trace.SpanTracer`
to its `count` and `total_ms` inside the measured window. The spans
exist only with obs on, which the traced run turns on; a program that
does not have the span (the parent of the PR that added it) leaves the
name out, and the reader returns nothing."""

from __future__ import annotations


def mean_ms(facts: dict, name: str) -> float | None:
    """Mean duration of span `name` over the window, in ms."""
    span = (facts.get("program_spans") or {}).get(name)
    if not span or not span.get("count"):
        return None
    return span["total_ms"] / span["count"]
