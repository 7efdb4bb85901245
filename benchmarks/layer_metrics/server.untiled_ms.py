"""What a `server.period` holds beyond its five children, per period:
(`server.period` − `server.collect` − `.stack` − `.dispatch` −
`.fetch` − `.scatter` totals) ÷ periods. The serve loop's own
remainder: the stats lock, `on_server_batch` with its `qsize()`, the
`server.ahead` / `server.batch` / `server.period` records, the loop's
test, and whatever wait for the GIL falls between two spans. Obs on
only."""

from benchmarks.harness import host_account


def read(facts: dict) -> float | None:
    return host_account.untiled_ms(facts)
