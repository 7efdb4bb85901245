"""Mean of the program span `ingest.batch`: per transport message, the
host decode/copy into staging (`ApexDriver._ingest_one` around
`_stage_one`), including any ship that message triggers. A program
that spans only stamped batches has none for loopback traffic. Obs on
only."""

from benchmarks.harness.span_stats import mean_ms


def read(facts: dict) -> float | None:
    return mean_ms(facts, "ingest.batch")
