"""Share of device busy time under the scope `ouro.mlp`
(models/ouro_q.OuroQNet._block: the dense SwiGLU MLP's three matmuls
and its activation), forward, recomputation and backward, every
application of every block, all four net applications of the loss, in
%, first chip. benchmarks/harness/ouro_scopes.py says how the scope's
time is read; a program without the scope leaves nothing to read."""

from benchmarks.harness import ouro_scopes


def read(facts: dict) -> float | None:
    return ouro_scopes.share_of_busy(facts, "ouro.mlp")
