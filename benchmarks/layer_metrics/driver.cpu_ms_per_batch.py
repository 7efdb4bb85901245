"""The WHOLE process's CPU a served batch: `time.process_time()` over
the window (the row `process.cpu` of the tracer's aggregates) ÷ the
server's batches in the window. Every Python thread and the runtime's
own are in it, the benchmark's client threads too — `query_batch` and
`send_experience` are the program's code on the caller's thread — so
work moved from one thread of the interpreter to another does not
leave it. In a traced run the profiler's threads are in it for their
two seconds. Obs on only."""

from benchmarks.harness import host_account


def read(facts: dict) -> float | None:
    return host_account.process_cpu_ms_per_batch(facts)
