"""The decoder family's `jax.named_scope`s (models/glm_moe_q.py), read
out of the run's trace with scope_stats.py's walk: that module's table
holds the R2D2 scopes only and takes the list as an argument, so the
decoder's readers ask here. Scopes nest (`glm.moe` contains
`glm.moe.router`, `.dispatch`, `.experts`, `.shared`; `glm.mla`
contains `glm.mla.scores`; the loss's `r2d2.burn_in`/`r2d2.unroll`
contain all of them), so shares do not add up. A program without the
scopes (a parent commit) gives an empty table and the readers return
nothing."""

from __future__ import annotations

import json

from benchmarks.harness import scope_stats
from benchmarks.harness.device import say

SCOPES = ("glm.embed", "glm.mla", "glm.mla.scores", "glm.moe",
          "glm.moe.router", "glm.moe.dispatch", "glm.moe.experts",
          "glm.moe.shared", "glm.dense_ffn", "glm.head", "r2d2.burn_in",
          "r2d2.unroll", "replay.sample_gather")


def of(facts: dict) -> dict[str, int]:
    """The run's table, computed once per result line and said on
    stderr as shares of busy time."""
    if "glm_scope_ns" not in facts:
        path = facts["runtime"].newest_xplane()
        facts["glm_scope_ns"] = (scope_stats.scope_times(path, SCOPES)
                                 if path else {})
        busy = max(facts["trace"]["devices"][0]["busy_ns"], 1)
        say("glm_scopes_% " + json.dumps({
            s: round(100.0 * ns / busy, 2)
            for s, ns in facts["glm_scope_ns"].items()}))
    return facts["glm_scope_ns"]


# XLA:TPU turns `jax.lax.ragged_dot` into grouped-matmul kernels that it
# names `ragged-dot-<...>` and gives NO name stack (seen on the v5e, PR
# 30: every other op of the expert layer carries `glm.moe.experts`), so
# no scope finds them: they are found by this name, category custom-call
KERNEL_PREFIX = "ragged-dot"


def grouped_matmul_ns(facts: dict) -> int:
    """Self time of the grouped-matmul kernels inside the traced
    window, first chip (0 when the program has none)."""
    ops = facts["trace"]["devices"][0].get("op_ns") or {}
    return sum(ns for label, ns in ops.items()
               if label.startswith(KERNEL_PREFIX)
               and "[custom-call]" in label)


def share_of_busy(facts: dict, scope: str) -> float | None:
    """Self time under `scope` / busy time, first chip, in %. The
    grouped-matmul kernels count under `glm.moe`, where they run."""
    busy = facts["trace"]["devices"][0]["busy_ns"]
    ns = of(facts).get(scope)
    if not ns or not busy:
        return None
    if scope == "glm.moe":
        ns += grouped_matmul_ns(facts)
    return 100.0 * ns / busy
