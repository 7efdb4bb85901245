"""Share of device busy time under the scope `r2d2.lstm_scan`
(models/lstm_q.ApeXLSTMQNet.__call__), forward and backward, burn-in
and trained segment, online and target net, in %, first chip: the
strictly sequential part of an R2D2 step, which no batch size fills.
benchmarks/harness/scope_stats.py says how a scope's time is read."""

from benchmarks.harness import scope_stats


def read(facts: dict) -> float | None:
    return scope_stats.share_of_busy(facts, "r2d2.lstm_scan")
