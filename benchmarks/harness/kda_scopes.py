"""The `jax.named_scope`s that models/kimi_linear_q.py and
ops/chunked_delta_rule.py add to the family's (`kimi.embed`, `kda`
around a KDA mixer and inside it `kda.proj`, `kda.conv`, `kda.gates`,
`kda.scan` - the op's own, with `kda.scan.intra` and `kda.scan.carry`
inside -, `kda.out`; `kimi.head`), read out of the run's trace with
scope_stats.py's walk - ouro_scopes.py's counterpart; the net's latent
attention and expert layer open `glm.mla*` and `glm.moe*`, which
glm_scopes.py reads. Scopes nest (an op is under every scope named in
its stack), so `kda`'s share CONTAINS `kda.scan`'s and that one its two
parts'. A program without the scopes (a parent commit, another net)
gives an empty table and the readers return nothing."""

from __future__ import annotations

import json

from benchmarks.harness import scope_stats
from benchmarks.harness.device import say

SCOPES = ("kimi.embed", "kda", "kda.proj", "kda.conv", "kda.gates",
          "kda.scan", "kda.scan.intra", "kda.scan.carry", "kda.out",
          "kimi.head")


def of(facts: dict) -> dict[str, int]:
    """The run's table, computed once per result line and said on
    stderr as shares of busy time."""
    if "kda_scope_ns" not in facts:
        path = facts["runtime"].newest_xplane()
        facts["kda_scope_ns"] = (scope_stats.scope_times(path, SCOPES)
                                 if path else {})
        busy = max(facts["trace"]["devices"][0]["busy_ns"], 1)
        say("kda_scopes_% " + json.dumps({
            s: round(100.0 * ns / busy, 2)
            for s, ns in facts["kda_scope_ns"].items()}))
    return facts["kda_scope_ns"]


def share_of_busy(facts: dict, scope: str) -> float | None:
    """Self time under `scope` / busy time, first chip, in %."""
    busy = facts["trace"]["devices"][0]["busy_ns"]
    ns = of(facts).get(scope)
    if not ns or not busy:
        return None
    return 100.0 * ns / busy
