"""The host threads' own account (ISSUE 52), read from the rows the
traffic kind copies out of `obs.trace.SpanTracer.aggregates()` into
`facts["program_spans"]` (`count` and `total_ms` of each name over the
measured window). A span that stamped the thread's CPU clock has a
second row, `<name>.cpu`, whose `total_ms` is CPU milliseconds and
whose `count` says how many spans stamped it — the tracer samples that
clock, so a `.cpu` row is read as a mean, total ÷ its OWN count, never
against the wall row's total, and on the chip's machines, whose CPU
clocks tick in steps of 10 ms, that mean swings by some 15% a run: a
diagnostic. What resolves a small step are the clocks read whole:
`process.cpu` (count 0) is `time.process_time()` over the window,
`thread.<role>.cpu` (count 0) a thread's own CPU clock over it; and
`host.gc` is the collector's pauses. A program without these rows (the
parent of the PR that added them) leaves the names out and every
reader here returns nothing."""

from __future__ import annotations

from benchmarks.harness.span_stats import mean_ms

CPU = ".cpu"
# the serve thread's spans that tile one `server.period`
PERIOD_CHILDREN = ("server.collect", "server.stack", "server.dispatch",
                   "server.fetch", "server.scatter")
# of those, the ones in which the thread has work in hand throughout:
# `server.collect` waits for requests and `server.fetch` for the device
# by design, so their time off the CPU says nothing
WORKING = ("server.stack", "server.dispatch", "server.scatter")


def total_ms(facts: dict, name: str) -> float | None:
    """`total_ms` of a row that may count nothing (`process.cpu`)."""
    span = (facts.get("program_spans") or {}).get(name)
    return None if span is None else span["total_ms"]


def untiled_ms(facts: dict) -> float | None:
    """What a period holds beyond its five children, per period."""
    period = (facts.get("program_spans") or {}).get("server.period")
    if not period or not period.get("count"):
        return None
    children = sum(total_ms(facts, name) or 0.0
                   for name in PERIOD_CHILDREN)
    return (period["total_ms"] - children) / period["count"]


def offcpu_ms(facts: dict) -> float | None:
    """Mean wall minus mean thread CPU of the working spans, a batch."""
    gap = 0.0
    for name in WORKING:
        wall, cpu = mean_ms(facts, name), mean_ms(facts, name + CPU)
        if wall is None or cpu is None:
            return None
        gap += wall - cpu
    return gap


def serve_thread_cpu_ms(facts: dict) -> float | None:
    """The serve thread's CPU over the window, a period."""
    cpu = total_ms(facts, "thread.inference-server.cpu")
    period = (facts.get("program_spans") or {}).get("server.period")
    if cpu is None or not period or not period.get("count"):
        return None
    return cpu / period["count"]


def process_cpu_ms_per_batch(facts: dict) -> float | None:
    """The whole process's CPU over the window, a served batch."""
    cpu = total_ms(facts, "process.cpu")
    batches = (facts.get("server_window") or {}).get("batches")
    if cpu is None or not batches:
        return None
    return cpu / batches
