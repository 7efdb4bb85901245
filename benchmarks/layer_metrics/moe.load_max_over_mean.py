"""How unevenly the routed rows fall on the experts held here: the
fullest held expert's rows over the mean of the held ones, online net
over a step's 8,192 tokens, mean over the expert layers and over the
window's dispatches. 1.0 is an even load; the grouped matmul's time
follows the sum, its tiles' fill the spread. Read from the step's own
metrics (`moe_load_max_over_mean`, runtime/family.decoder_q_family); a
program without the counter leaves nothing to read."""


def read(facts: dict) -> float | None:
    return (facts.get("moe") or {}).get("load_max_over_mean")
