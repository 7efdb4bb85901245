"""1 - union of device-op intervals / traced window, in %, for a cell
that drives the server alone: what the host (clients' argmax and token
rule, the serve thread's stack and scatter of a vocabulary-wide reply)
leaves of the chip. None for a kind without the slot server's `decode`
facts."""


def read(facts: dict) -> float | None:
    if not facts.get("decode"):
        return None
    return 100.0 * facts["trace"]["idle_share_worst"]
