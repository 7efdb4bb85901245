"""Share of device busy time under the scope `r2d2.burn_in`
(ops/losses.make_r2d2_loss), in %, first chip: torso, scan and head of
the online and target nets over the half of every sequence that yields
no gradient."""

from benchmarks.harness import scope_stats


def read(facts: dict) -> float | None:
    return scope_stats.share_of_busy(facts, "r2d2.burn_in")
