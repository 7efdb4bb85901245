"""The learner cycle's own `jax.named_scope`s (runtime/learner.py::
CYCLE_SCOPES and the two of ops/sum_tree.py, ISSUE 35), read out of the
run's trace with scope_stats.py's walk - glm_scopes.py's counterpart for
the cycle every family runs (that module's table is its family's and
takes no list).

The seven `cycle.*` names are disjoint and tile a grad step: sample
(descent, storage gather, IS weights, the K-split), batch (items -> the
family's batch), loss_grad (the family's loss forward and backward: its
own scopes nest inside), optimizer, target_sync, health (the metrics a
step computes on the device), write_back. So their shares DO add up, and
what is left of busy time is the account's residual: ops under no name -
the train loop's own `while` time, the rng split, copies XLA inserts
without metadata, a fusion that took its name from an op outside the
cycle. `sum_tree.descent` nests in `cycle.sample`, `sum_tree.update` in
`cycle.write_back`; a `sum_tree.update` outside `cycle.write_back` is an
ingest add (pong_live). A `while` with no name stack of its own counts
under a scope when 90% of what runs in it does (scope_stats.py), so the
train loop's own time goes to `cycle.loss_grad` where the loss is nearly
all of a step. XLA:TPU's grouped-matmul kernels (`ragged-dot-*`,
glm_scopes.py) carry no name stack and run only inside the decoder
family's loss: they count under `cycle.loss_grad`, as they do under
`glm.moe`. A program without the scopes (a parent commit, or an
executable the compile cache kept from one: the cache's key leaves
debug info out) gives a table of zeros and every reader returns
nothing.

    python3 -m benchmarks.harness.cycle_scopes <trace.xplane.pb>

says the table of any trace (ns and % of the first chip's busy time)."""

from __future__ import annotations

import json

from benchmarks.harness import glm_scopes, scope_stats
from benchmarks.harness.device import say

# the program's names, copied: a rename there must fail a test here
# (benchmarks/tests/test_cycle_scopes.py), not silence six metrics
TOP_LEVEL = ("cycle.sample", "cycle.batch", "cycle.loss_grad",
             "cycle.optimizer", "cycle.target_sync", "cycle.health",
             "cycle.write_back")
NESTED = ("sum_tree.descent", "sum_tree.update")
SCOPES = TOP_LEVEL + NESTED
UNSCOPED = "unscoped"


def shares(table: dict[str, int], trace: dict) -> dict[str, float]:
    """{scope: ns} and trace_reduce's reduction of the same trace ->
    {scope: % of the first chip's busy time}, with the residual
    `unscoped` = 100 - the seven top-level shares; {} when none of the
    seven was found (then the residual is not 100: there is no
    account)."""
    busy_ns = trace["devices"][0]["busy_ns"]
    if not busy_ns or not any(table.get(s) for s in TOP_LEVEL):
        return {}
    ns = {s: table.get(s, 0) for s in SCOPES}
    ns["cycle.loss_grad"] += glm_scopes.grouped_matmul_ns({"trace": trace})
    out = {s: 100.0 * ns[s] / busy_ns for s in SCOPES}
    out[UNSCOPED] = 100.0 - sum(out[s] for s in TOP_LEVEL)
    return out


def of(facts: dict) -> dict[str, float]:
    """The run's account in % of busy time, first chip, computed once
    per result line and said on stderr."""
    if "cycle_scope_share" not in facts:
        path = facts["runtime"].newest_xplane()
        table = scope_stats.scope_times(path, SCOPES) if path else {}
        facts["cycle_scope_share"] = shares(table, facts["trace"])
        say("cycle_scopes_% " + json.dumps({
            s: round(v, 2)
            for s, v in facts["cycle_scope_share"].items()}))
    return facts["cycle_scope_share"]


def share_of_busy(facts: dict, scope: str) -> float | None:
    """Self time under `scope` / busy time, first chip, in %; the
    residual under the name `unscoped`. None where the trace has no
    cycle scope at all, or none of this one."""
    value = of(facts).get(scope)
    # a scope the program opens around no op reads as nothing, not 0
    return value if scope == UNSCOPED else value or None


def main(argv: list[str]) -> int:
    import time

    from benchmarks.harness import trace_reduce

    if len(argv) != 1:
        print("usage: python3 -m benchmarks.harness.cycle_scopes "
              "<trace.xplane.pb>")
        return 2
    trace = trace_reduce.reduce(argv[0])
    t0 = time.monotonic()
    table = scope_stats.scope_times(argv[0], SCOPES)
    seconds = time.monotonic() - t0
    print(json.dumps({"busy_ns": trace["devices"][0]["busy_ns"],
                      "scope_ns": table,
                      "share_%": {s: round(v, 3) for s, v in
                                  shares(table, trace).items()},
                      "scope_pass_s": round(seconds, 2)}))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main(sys.argv[1:]))
