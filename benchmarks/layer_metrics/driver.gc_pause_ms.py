"""Milliseconds of the window the cyclic collector held a thread: total
of the program span `host.gc`, one per collection of any generation
from the tracer's `gc.callbacks` hook (the trace JSON keeps each with
its generation). 0 for a window without one. Obs on only."""

from benchmarks.harness import host_account


def read(facts: dict) -> float | None:
    return host_account.total_ms(facts, "host.gc")
