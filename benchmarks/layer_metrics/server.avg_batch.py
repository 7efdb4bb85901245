"""Observations per server forward in the window: delta of
`server.stats` items / batches (64 is a full bucket)."""


def read(facts: dict) -> float | None:
    s = facts.get("server_window")
    if not s or s["batches"] <= 0:
        return None
    return s["items"] / s["batches"]
