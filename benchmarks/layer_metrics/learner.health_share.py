"""Share of device busy time under the scope `cycle.health`
(runtime/learner.py: `optax.global_norm(grads)`, the step's metrics,
`obs/learning.py::sgd_diag`, both `_replay_health` calls, the sharded
learner's shard means): what the observability INSIDE the jitted step
costs, in %, first chip. The clip's norm and the health norm are one
expression after CSE; whichever op survives keeps its scope (seen on
the v5e, PR 35: the clip's, under `cycle.optimizer`, in the pixel
cells). benchmarks/harness/cycle_scopes.py says how the cycle's account
is read."""

from benchmarks.harness import cycle_scopes


def read(facts: dict) -> float | None:
    return cycle_scopes.share_of_busy(facts, "cycle.health")
