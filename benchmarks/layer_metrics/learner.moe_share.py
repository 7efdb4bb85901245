"""Share of device busy time under the scope `glm.moe`
(models/glm_moe_q.GlmMoeQNet._block: router, dispatch — sort, gather,
scatter-add —, the grouped expert matmuls and the shared expert),
forward, recomputation and backward, all four net applications of the
loss, in %, first chip. The grouped-matmul kernels carry no name stack
and are added by name. benchmarks/harness/glm_scopes.py says how a
scope's time is read."""

from benchmarks.harness import glm_scopes


def read(facts: dict) -> float | None:
    return glm_scopes.share_of_busy(facts, "glm.moe")
