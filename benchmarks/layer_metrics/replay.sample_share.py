"""Share of device busy time under the scope `cycle.sample`
(runtime/learner.py::_sample_stage, `_train_step`'s `_sample_weighted`,
and the sharded learner's overrides): the stratified sum-tree descent
(`sum_tree.descent`), the storage gather (`replay.sample_gather`), the
IS weights, the descent-time leaf priorities and the K-split stacks, in
%, first chip. benchmarks/harness/cycle_scopes.py says how the cycle's
account is read and what its residual is."""

from benchmarks.harness import cycle_scopes


def read(facts: dict) -> float | None:
    return cycle_scopes.share_of_busy(facts, "cycle.sample")
