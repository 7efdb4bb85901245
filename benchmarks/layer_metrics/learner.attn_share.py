"""Share of device busy time under the scope `afmoe.attn`
(models/afmoe_q.AfmoeQNet._block: the q, k, v and gate projections, the
head norms, RoPE, the blockwise attention, the output gate and
projection), forward, recomputation and backward, all four net
applications of the loss, in %, first chip. The attention is XLA ops
that carry their name stack, so nothing is added by name.
benchmarks/harness/afmoe_scopes.py says how a scope's time is read."""

from benchmarks.harness import afmoe_scopes


def read(facts: dict) -> float | None:
    return afmoe_scopes.share_of_busy(facts, "afmoe.attn")
