"""The serve thread's CPU a batch: the thread's own CPU clock over the
window (the row `thread.inference-server.cpu` of the tracer's
aggregates: the clock at the thread's latest stamp, so two snapshots
bracket it to within a 20th of a second of the thread's work) ÷ the
window's `server.period`s. Exact to the clock's tick, where the
`<span>.cpu` means are samples. Beside `server.period_ms` it says how
much of a period the thread was on a core at all. Obs on only."""

from benchmarks.harness import host_account


def read(facts: dict) -> float | None:
    return host_account.serve_thread_cpu_ms(facts)
