"""Share of device busy time under the scope `st.route_ahead`
(models/smallthinker_q.SmallThinkerQNet._block): the router's matmul on
the attention's normed input and the expert layer's whole plan - top-k,
softmax over the selected logits, the sort by held expert, its inverse
and the counts - which in this net is made BEFORE attention and waits
for nothing attention computes; forward, recomputation and backward,
all four net applications of the loss, in %, first chip. Lower is
better: it is the part of the expert layer that is not matmuls. The
plan's ops also carry `glm.moe.router` / `glm.moe.dispatch`, so
`learner.moe_share` counts them too (the two shares overlap; they do
not add). benchmarks/harness/smallthinker_scopes.py says how the
scope's time is read; a program without the scope leaves nothing to
read."""

from benchmarks.harness import smallthinker_scopes


def read(facts: dict) -> float | None:
    return smallthinker_scopes.share_of_busy(facts, "st.route_ahead")
