"""Share of device busy time under the scope `glm.mla`
(models/glm_moe_q.GlmMoeQNet._block: the low-rank projections, rotary
embedding, scores and softmax under `glm.mla.scores`, the output
projection), forward, recomputation and backward, all four net
applications of the loss, in %, first chip.
benchmarks/harness/glm_scopes.py says how a scope's time is read."""

from benchmarks.harness import glm_scopes


def read(facts: dict) -> float | None:
    return glm_scopes.share_of_busy(facts, "glm.mla")
