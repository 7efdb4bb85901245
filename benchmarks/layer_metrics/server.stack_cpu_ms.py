"""Thread-CPU mean of the program span `server.stack` (the row
`server.stack.cpu`): what stacking a batch costs in work, beside
`server.stack_ms`, which also holds the wait to get the interpreter
back. On the chip's machines the thread-CPU clock ticks in
steps of 10 ms and the tracer samples it (some 575 stamped spans a 30 s
window), so this mean swings by about 15% a run: a diagnostic to read
over several runs, not a witness of a small step —
`driver.cpu_ms_per_batch` and `server.cpu_ms` are those. Obs on only."""

from benchmarks.harness.span_stats import mean_ms


def read(facts: dict) -> float | None:
    return mean_ms(facts, "server.stack.cpu")
