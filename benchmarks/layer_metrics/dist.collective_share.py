"""Device time in collective ops (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all) / traced window, in %,
on the chip where it is largest."""


def read(facts: dict) -> float | None:
    tr = facts["trace"]
    return 100.0 * max(d["collective_ns"] for d in tr["devices"]) / (
        tr["window_s"] * 1e9)
