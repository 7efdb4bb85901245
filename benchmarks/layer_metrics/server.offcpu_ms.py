"""Time a batch during which the serve thread had work in hand and was
on no core: Σ over the program spans `server.stack`, `server.dispatch`
and `server.scatter` of (mean wall − mean thread CPU). It holds the wait
for the GIL, the params lock, a transfer the runtime waits for and a
descheduled thread alike: it says that the thread waited, not for
what. On the chip's machines the thread-CPU clock ticks in
steps of 10 ms and the tracer samples it (some 575 stamped spans a 30 s
window), so this mean swings by about 15% a run: a diagnostic to read
over several runs, not a witness of a small step —
`driver.cpu_ms_per_batch` and `server.cpu_ms` are those. Obs on only."""

from benchmarks.harness import host_account


def read(facts: dict) -> float | None:
    return host_account.offcpu_ms(facts)
