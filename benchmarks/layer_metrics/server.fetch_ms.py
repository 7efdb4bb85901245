"""Mean of the program span `server.fetch`: per batch, `np.asarray` on
the outputs — device time, device-to-host copy and the serve thread's
wait to get the GIL back (`BatchedInferenceServer._serve_batch`). Obs
on only."""

from benchmarks.harness.span_stats import mean_ms


def read(facts: dict) -> float | None:
    return mean_ms(facts, "server.fetch")
