"""Thread-CPU mean of the program span `replay.add` (the row
`replay.add.cpu`), beside `ingest.add_ms`: whether an add that got
slower does more work or only waits longer for the interpreter. On the chip's machines the thread-CPU clock ticks in
steps of 10 ms and the tracer samples it (some 575 stamped spans a 30 s
window), so this mean swings by about 15% a run: a diagnostic to read
over several runs, not a witness of a small step —
`driver.cpu_ms_per_batch` and `server.cpu_ms` are those. Obs on
only."""

from benchmarks.harness.span_stats import mean_ms


def read(facts: dict) -> float | None:
    return mean_ms(facts, "replay.add.cpu")
