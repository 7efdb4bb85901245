"""Share of device busy time under the scope `cycle.loss_grad`
(the `jax.value_and_grad(family.loss_fn)` call of
runtime/learner.py::_sgd_update): the family's loss, forward,
recomputation and backward, every net application of it - the part of
a step that is the model; the families' own scopes (`r2d2.*`, `glm.*`,
`afmoe.*`) nest inside. In %, first chip. The grouped-matmul kernels
carry no name stack and are added by name; where the loss is 90% of a
step or more the train loop's own `while` time counts here too; in the
pixel cells so does the Adam that XLA fuses into the weight-gradient
convolutions; RoPE's multi-output fusions and layout copies carry no
metadata and do NOT (benchmarks/harness/cycle_scopes.py, PERF.md
section 6, PR 35)."""

from benchmarks.harness import cycle_scopes


def read(facts: dict) -> float | None:
    return cycle_scopes.share_of_busy(facts, "cycle.loss_grad")
