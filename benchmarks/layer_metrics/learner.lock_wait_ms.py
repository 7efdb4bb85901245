"""Mean of the program span `state_lock.wait.learner`: per train
dispatch, the learner thread's wait from asking for
`ApexDriver._state_lock` to holding it (`_learner_loop_inner`). Obs on
only."""

from benchmarks.harness.span_stats import mean_ms


def read(facts: dict) -> float | None:
    return mean_ms(facts, "state_lock.wait.learner")
