"""The `jax.named_scope`s that models/jamba_q.py opens (`jamba.embed`;
`jamba.mamba` with `.in`, `.conv`, `.gates`, `.scan`, `.out`;
`jamba.attn` with `.proj`, `.attend`, `.out`; `jamba.mlp`; `jamba.head`;
`slots.read` / `slots.write`), read out of the run's trace with
scope_stats.py's walk - sala_scopes.py's counterpart. Scopes nest, so
`jamba.mamba`'s share CONTAINS its parts', and `jamba.mamba.scan`
contains the state's `slots.read` / `slots.write`. A program without the
scopes (a parent commit, another net) gives an empty table and the
readers return nothing. The step's device time is sala_scopes.py's (the
server's slot program is `apply_slots` whatever the net)."""

from __future__ import annotations

import json

from benchmarks.harness import scope_stats
from benchmarks.harness.device import say
from benchmarks.harness.sala_scopes import step_seconds  # noqa: F401

SCOPES = ("jamba.embed", "jamba.mamba", "jamba.mamba.in", "jamba.mamba.conv",
          "jamba.mamba.gates", "jamba.mamba.scan", "jamba.mamba.out",
          "jamba.attn", "jamba.attn.proj", "jamba.attn.attend",
          "jamba.attn.out", "jamba.mlp", "jamba.head", "slots.read",
          "slots.write")


def of(facts: dict) -> dict[str, int]:
    """The run's table, computed once per result line and said on
    stderr as shares of busy time."""
    if "jamba_scope_ns" not in facts:
        path = facts["runtime"].newest_xplane()
        facts["jamba_scope_ns"] = (scope_stats.scope_times(path, SCOPES)
                                   if path else {})
        busy = max(facts["trace"]["devices"][0]["busy_ns"], 1)
        say("jamba_scopes_% " + json.dumps({
            s: round(100.0 * ns / busy, 2)
            for s, ns in facts["jamba_scope_ns"].items()}))
    return facts["jamba_scope_ns"]


def share_of_busy(facts: dict, scope: str) -> float | None:
    """Self time under `scope` / busy time, first chip, in %."""
    busy = facts["trace"]["devices"][0]["busy_ns"]
    ns = of(facts).get(scope)
    if not ns or not busy:
        return None
    return 100.0 * ns / busy


def seconds_per_step(facts: dict, scope: str) -> float | None:
    """Device seconds a decode step spends under `scope`: its self time
    in the traced window / the slot program's executions there."""
    dev = facts["trace"]["devices"][0]
    steps = sum(m["count"] for name, m in dev["modules"].items()
                if "apply_slots" in name)
    ns = of(facts).get(scope)
    if not steps or not ns:
        return None
    return ns / steps / 1e9
