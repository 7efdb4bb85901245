"""The `jax.named_scope`s that models/minicpm_sala_q.py opens
(`sala.embed`; `sala.lightning` with `.proj`, `.state`, `.out`;
`sala.sparse` with `.proj`, `.compress`, `.select`, `.attend`, `.dense`,
`.out`; `sala.mlp`; `sala.head`; `slots.read` / `slots.write`), read out
of the run's trace with scope_stats.py's walk - kda_scopes.py's
counterpart. Scopes nest, so `sala.sparse`'s share CONTAINS its parts'.
A program without the scopes (a parent commit, another net) gives an
empty table and the readers return nothing."""

from __future__ import annotations

import json

from benchmarks.harness import scope_stats
from benchmarks.harness.device import say

SCOPES = ("sala.embed", "sala.lightning", "sala.lightning.proj",
          "sala.lightning.state", "sala.lightning.out", "sala.sparse",
          "sala.sparse.proj", "sala.sparse.compress", "sala.sparse.select",
          "sala.sparse.attend", "sala.sparse.dense", "sala.sparse.out",
          "sala.mlp", "sala.head", "slots.read", "slots.write")


def of(facts: dict) -> dict[str, int]:
    """The run's table, computed once per result line and said on
    stderr as shares of busy time."""
    if "sala_scope_ns" not in facts:
        path = facts["runtime"].newest_xplane()
        facts["sala_scope_ns"] = (scope_stats.scope_times(path, SCOPES)
                                  if path else {})
        busy = max(facts["trace"]["devices"][0]["busy_ns"], 1)
        say("sala_scopes_% " + json.dumps({
            s: round(100.0 * ns / busy, 2)
            for s, ns in facts["sala_scope_ns"].items()}))
    return facts["sala_scope_ns"]


def share_of_busy(facts: dict, scope: str) -> float | None:
    """Self time under `scope` / busy time, first chip, in %."""
    busy = facts["trace"]["devices"][0]["busy_ns"]
    ns = of(facts).get(scope)
    if not ns or not busy:
        return None
    return 100.0 * ns / busy


def step_seconds(facts: dict) -> float | None:
    """Device seconds of one decode step: the median execution of the
    server's slot program in the trace's `XLA Modules` line (the window
    holds decode steps alone)."""
    ns = max((m["median_ns"]
              for name, m in facts["trace"]["devices"][0]["modules"].items()
              if "apply_slots" in name), default=0)
    return ns / 1e9 or None


def seconds_per_step(facts: dict, *scopes: str) -> float | None:
    """Device seconds a decode step spends under `scopes` (summed): each
    scope's share of busy time x the busy time a step, from the one
    trace."""
    dev = facts["trace"]["devices"][0]
    steps = sum(m["count"] for name, m in dev["modules"].items()
                if "apply_slots" in name)
    total = sum(of(facts).get(s, 0) for s in scopes)
    if not steps or not total:
        return None
    return total / steps / 1e9
