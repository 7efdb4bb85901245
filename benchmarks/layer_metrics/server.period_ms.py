"""Mean wall time of the program span `server.period`: the serve
thread's time a batch answered, loop top to loop bottom
(`BatchedInferenceServer._serve_loop`) — one iteration while a forward
is in flight, the dispatching and the replying iteration together
otherwise. Periods tile the thread's time but for its idle polls, so
mean × count is the share of the window the thread was serving. Obs on
only."""

from benchmarks.harness.span_stats import mean_ms


def read(facts: dict) -> float | None:
    return mean_ms(facts, "server.period")
