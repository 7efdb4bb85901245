"""Transitions the transport or the stager dropped (and counted) /
transitions offered, in %, over the whole run: `transport.dropped`
plus the run summary's `ingest_dropped` share that is the stager's
(`_stage_dropped` is private and only the summary carries it)."""


def read(facts: dict) -> float | None:
    ledger = facts.get("ingest_ledger")
    if not ledger or ledger["offered"] <= 0:
        return None
    return 100.0 * ledger["dropped"] / ledger["offered"]
