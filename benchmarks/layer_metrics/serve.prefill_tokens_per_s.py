"""Tokens a second of the set-up's prefill through the server's chunked
path (every client's sessions, `inference.prefill_chunk` tokens a row):
the chunked scan's only reading, on the host's clock, for a family
served from slots with a recurrence (`jamba_slots`). None without the
kind's `prefill` facts or for another family."""

from benchmarks.harness import flops_jamba as counts


def read(facts: dict) -> float | None:
    prefill = facts.get("prefill")
    if not prefill or not prefill.get("seconds") or (
            facts.get("family") != counts.FAMILY):
        return None
    return prefill["tokens"] / prefill["seconds"]
