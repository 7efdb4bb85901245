"""Share of device busy time under the scope `kda`
(models/kimi_linear_q.KimiLinearQNet._block: a Kimi Delta Attention
mixer whole - the four projections, the three short convolutions, the
gates, the delta rule's scan, the output norm and gate), forward,
recomputation and backward, all four net applications of the loss, in
%, first chip. It CONTAINS `learner.kda_scan_share` (scopes nest).
benchmarks/harness/kda_scopes.py says how the scope's time is read; a
program without the scope leaves nothing to read."""

from benchmarks.harness import kda_scopes


def read(facts: dict) -> float | None:
    return kda_scopes.share_of_busy(facts, "kda")
