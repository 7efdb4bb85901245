"""What the selection left of the context, in %: the program's counter
`sparse_blocks_attended` / `sparse_blocks_in_context` over the window
(each summed over rows, sparse layers and steps inside the device
program). A number the seed's lengths decide: 64 blocks of a context of
c positions is 64 / (c / 64 + 1). None without the counters."""


def read(facts: dict) -> float | None:
    c = facts.get("slot_counters") or {}
    if not c.get("sparse_blocks_in_context"):
        return None
    return (100.0 * c.get("sparse_blocks_attended", 0)
            / c["sparse_blocks_in_context"])
