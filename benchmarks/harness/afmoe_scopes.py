"""The `jax.named_scope`s of the decoder family's second net
(models/afmoe_q.py), read out of the run's trace with scope_stats.py's
walk - glm_scopes.py's counterpart (that module's table is GLM's and
takes no list). `afmoe.attn` (projections, head norms, RoPE, the
attention itself, gate, output projection) contains
`afmoe.attn.sliding` / `afmoe.attn.full`, which hold
ops/blockwise_attention.py's call alone, by layer kind; the expert
layer's scopes are `glm.moe*` for both nets and glm_scopes.py reads
them. Scopes nest, so shares do not add up. The attention is plain XLA
ops, every one of which carries its name stack (forward, recomputation
and backward alike), so no kernel has to be found by name. A program
without the scopes (a parent commit) gives an empty table and the
readers return nothing."""

from __future__ import annotations

import json

from benchmarks.harness import scope_stats
from benchmarks.harness.device import say

SCOPES = ("afmoe.embed", "afmoe.attn", "afmoe.attn.sliding",
          "afmoe.attn.full", "afmoe.dense_ffn", "afmoe.head")
KERNEL_SCOPES = ("afmoe.attn.sliding", "afmoe.attn.full")


def of(facts: dict) -> dict[str, int]:
    """The run's table, computed once per result line and said on
    stderr as shares of busy time."""
    if "afmoe_scope_ns" not in facts:
        path = facts["runtime"].newest_xplane()
        facts["afmoe_scope_ns"] = (scope_stats.scope_times(path, SCOPES)
                                   if path else {})
        busy = max(facts["trace"]["devices"][0]["busy_ns"], 1)
        say("afmoe_scopes_% " + json.dumps({
            s: round(100.0 * ns / busy, 2)
            for s, ns in facts["afmoe_scope_ns"].items()}))
    return facts["afmoe_scope_ns"]


def kernel_ns(facts: dict) -> int:
    """Self time of the blockwise attention, both layer kinds."""
    table = of(facts)
    return sum(table.get(s, 0) for s in KERNEL_SCOPES)


def share_of_busy(facts: dict, scope: str) -> float | None:
    """Self time under `scope` / busy time, first chip, in %."""
    busy = facts["trace"]["devices"][0]["busy_ns"]
    ns = of(facts).get(scope)
    if not ns or not busy:
        return None
    return 100.0 * ns / busy
