"""Share of device busy time under the scope `lfm2.conv`
(models/lfm2_moe_q.Lfm2MoeQNet._block: a gated short-convolution
operator whole - W_in, both gates, the causal depthwise filter, W_out),
forward, recomputation and backward, all four net applications of the
loss, in %, first chip. It CONTAINS `learner.conv_mix_share` (scopes
nest). benchmarks/harness/lfm2_scopes.py says how the scope's time is
read; a program without the scope leaves nothing to read."""

from benchmarks.harness import lfm2_scopes


def read(facts: dict) -> float | None:
    return lfm2_scopes.share_of_busy(facts, "lfm2.conv")
