"""Device time of one decode step of a slot server: the median duration
of the server's slot program's executions (`jit_apply_slots`) in the
trace's `XLA Modules` line. The window holds decode steps alone (the
prefill is set-up). None where the trace has no such program (a
stateless server, a parent commit)."""

from benchmarks.harness import sala_scopes


def read(facts: dict) -> float | None:
    s = sala_scopes.step_seconds(facts)
    return s * 1e3 if s else None
