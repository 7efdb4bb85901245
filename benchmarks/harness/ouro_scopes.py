"""The `jax.named_scope`s that models/ouro_q.py adds to the family's
(`ouro.embed`, `ouro.loop` around the scan over the loop steps, and
inside it `ouro.mlp` and `ouro.norms`; `ouro.head`), read out of the
run's trace with scope_stats.py's walk - smallthinker_scopes.py's
counterpart; the net's attention opens `afmoe.attn*` inside
`ouro.loop`, which afmoe_scopes.py reads. Scopes nest (an op is under
every scope named in its stack), so `ouro.loop`'s share CONTAINS the
attention's, the MLP's and the norms'. A program without the scopes (a
parent commit, another net) gives an empty table and the readers return
nothing."""

from __future__ import annotations

import json

from benchmarks.harness import scope_stats
from benchmarks.harness.device import say

SCOPES = ("ouro.embed", "ouro.loop", "ouro.mlp", "ouro.norms", "ouro.head")


def of(facts: dict) -> dict[str, int]:
    """The run's table, computed once per result line and said on
    stderr as shares of busy time."""
    if "ouro_scope_ns" not in facts:
        path = facts["runtime"].newest_xplane()
        facts["ouro_scope_ns"] = (scope_stats.scope_times(path, SCOPES)
                                  if path else {})
        busy = max(facts["trace"]["devices"][0]["busy_ns"], 1)
        say("ouro_scopes_% " + json.dumps({
            s: round(100.0 * ns / busy, 2)
            for s, ns in facts["ouro_scope_ns"].items()}))
    return facts["ouro_scope_ns"]


def share_of_busy(facts: dict, scope: str) -> float | None:
    """Self time under `scope` / busy time, first chip, in %."""
    busy = facts["trace"]["devices"][0]["busy_ns"]
    ns = of(facts).get(scope)
    if not ns or not busy:
        return None
    return 100.0 * ns / busy
