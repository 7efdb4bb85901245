"""Share of device busy time under the scope `cycle.write_back`
(`_write_back` at its two call sites in runtime/learner.py: the ONE
priority write-back of a step or macro-step, `sum_tree.update` over the
K*B drawn leaves and their ancestors), in %, first chip. An ingest
add's tree update carries `sum_tree.update` without this name and is
not counted. benchmarks/harness/cycle_scopes.py says how the cycle's
account is read."""

from benchmarks.harness import cycle_scopes


def read(facts: dict) -> float | None:
    return cycle_scopes.share_of_busy(facts, "cycle.write_back")
