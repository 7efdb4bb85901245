"""The `jax.named_scope`s that models/smallthinker_q.py adds to the
family's (`st.embed`, `st.route_ahead`, `st.head`), read out of the
run's trace with scope_stats.py's walk - afmoe_scopes.py's counterpart;
the net's attention and expert layer open `afmoe.attn*` and `glm.moe*`,
which afmoe_scopes.py and glm_scopes.py read. `st.route_ahead` holds
the router's matmul on the attention's input and the whole plan (top-k,
softmax over the selected, sort, inverse, counts), whose ops ALSO carry
models/expert_layer.py's `glm.moe.router` / `glm.moe.dispatch` nested
inside it: an op is under every scope named in its stack, so
`learner.moe_share` (`glm.moe`) counts them too. A program without the
scopes (a parent commit, another net) gives an empty table and the
reader returns nothing."""

from __future__ import annotations

import json

from benchmarks.harness import scope_stats
from benchmarks.harness.device import say

SCOPES = ("st.embed", "st.route_ahead", "st.head")


def of(facts: dict) -> dict[str, int]:
    """The run's table, computed once per result line and said on
    stderr as shares of busy time."""
    if "st_scope_ns" not in facts:
        path = facts["runtime"].newest_xplane()
        facts["st_scope_ns"] = (scope_stats.scope_times(path, SCOPES)
                                if path else {})
        busy = max(facts["trace"]["devices"][0]["busy_ns"], 1)
        say("st_scopes_% " + json.dumps({
            s: round(100.0 * ns / busy, 2)
            for s, ns in facts["st_scope_ns"].items()}))
    return facts["st_scope_ns"]


def share_of_busy(facts: dict, scope: str) -> float | None:
    """Self time under `scope` / busy time, first chip, in %."""
    busy = facts["trace"]["devices"][0]["busy_ns"]
    ns = of(facts).get(scope)
    if not ns or not busy:
        return None
    return 100.0 * ns / busy
