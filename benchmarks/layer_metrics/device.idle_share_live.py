"""1 - union of device-op intervals / traced window, in %, on the
chip that idles most."""


def read(facts: dict) -> float | None:
    return 100.0 * facts["trace"]["idle_share_worst"]
