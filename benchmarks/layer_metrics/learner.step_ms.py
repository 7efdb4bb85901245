"""Device time per grad step: the median duration of the train
program's executions in the trace's `XLA Modules` line, on the chip
where it is longest / the grad steps one dispatch holds."""


def read(facts: dict) -> float | None:
    chunk = facts.get("train_chunk")
    if not chunk:
        return None
    worst = max((m["median_ns"] for dev in facts["trace"]["devices"]
                 for name, m in dev["modules"].items()
                 if "train_many" in name), default=0)
    return worst / chunk / 1e6 or None
