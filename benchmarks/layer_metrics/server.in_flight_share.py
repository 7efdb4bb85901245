"""Share (%) of the window's server batches that were dispatched while
their predecessor was still unfetched: count of the program span
`server.ahead` ÷ count of `server.batch`
(`BatchedInferenceServer._serve_loop`: batch k+1 goes ahead of batch
k's fetch only when its requests are already waiting). 100 is a serve
thread that always has one forward in flight; 0 one that waits for
every reply before it looks at the queue. Obs on only; a program with
no `server.ahead` span at all (one from before the pipeline) gives
nothing."""


def read(facts: dict) -> float | None:
    spans = facts.get("program_spans") or {}
    ahead, batches = spans.get("server.ahead"), spans.get("server.batch")
    if ahead is None or not batches or not batches.get("count"):
        return None
    return 100.0 * ahead["count"] / batches["count"]
