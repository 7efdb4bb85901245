"""Share of device busy time under the scope `kda.scan`
(ops/chunked_delta_rule.py: the `lax.scan` over chunks - each chunk's
tiles, its triangular solve and the three products that carry the
state - and the reshapes around it), forward, recomputation and
backward, all four net applications of the loss, in %, first chip: the
share of a step that the delta rule itself is, apart from the matmuls
that feed it. benchmarks/harness/kda_scopes.py says how the scope's
time is read; a program without the scope leaves nothing to read."""

from benchmarks.harness import kda_scopes


def read(facts: dict) -> float | None:
    return kda_scopes.share_of_busy(facts, "kda.scan")
