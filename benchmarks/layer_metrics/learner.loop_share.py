"""Share of device busy time under the scope `ouro.loop`
(models/ouro_q.OuroQNet.apply_with_stats: the `lax.scan` over the loop
steps - every application of every block, the loop's final norm and the
exit gate), forward, recomputation and backward, all four net
applications of the loss, in %, first chip: the share of a step that
the looped stack is. It CONTAINS `learner.attn_share` and
`learner.dense_ffn_share` (scopes nest). What is outside it: embedding,
head, the loss, Adam, the replay. benchmarks/harness/ouro_scopes.py
says how the scope's time is read; a program without the scope leaves
nothing to read."""

from benchmarks.harness import ouro_scopes


def read(facts: dict) -> float | None:
    return ouro_scopes.share_of_busy(facts, "ouro.loop")
