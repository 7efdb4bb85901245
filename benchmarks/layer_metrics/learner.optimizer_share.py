"""Share of device busy time under the scope `cycle.optimizer`
(runtime/learner.py::_sgd_update: `optimizer.update`, the clip and
Adam, and `optax.apply_updates`), in %, first chip. A fusion has one
name, its root's: where XLA:TPU fuses Adam (and the target sync's
select) into a weight-gradient convolution as its output fusion - every
matrix leaf of the pixel cells - that time reads under
`cycle.loss_grad`, and this is the biases' Adam and the clip's norm
(PERF.md section 6, PR 35); in the decoder cells Adam is its own
fusions and all here. benchmarks/harness/cycle_scopes.py says how the
cycle's account is read."""

from benchmarks.harness import cycle_scopes


def read(facts: dict) -> float | None:
    return cycle_scopes.share_of_busy(facts, "cycle.optimizer")
