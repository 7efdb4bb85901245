"""Set-up fill: transitions written into the ring / fenced seconds.
Device-side `learner.add` in the offline mix, the whole public ingest
path (transport, stager, `add_many`) in the fleet mix."""


def read(facts: dict) -> float | None:
    fill = facts.get("fill")
    if not fill or fill["seconds"] <= 0:
        return None
    return fill["transitions"] / fill["seconds"]
