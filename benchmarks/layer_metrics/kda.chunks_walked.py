"""Chunks the delta rule's scan walked per forward pass of the online
net (prefix and trained steps), summed over the KDA layers in the
scan's own carry where a chunk is walked: the step's counter
`kda_chunks` (runtime/family.decoder_q_family, for a net with a scan
layer; ops/chunked_delta_rule.py adds one at every chunk), mean over
the window's dispatches, read through the traffic kind's
`facts["kda"]["chunks_walked"]`. It has to read KDA layers x (burn-in +
trained positions) / the chunk (4 x 4,096 / 32 = 512 in
`kimi_linear_offline`): fewer is a layer or a stretch of the sequence
that was skipped. A kind that does not carry the counter leaves nothing
to read."""


def read(facts: dict) -> float | None:
    return (facts.get("kda") or {}).get("chunks_walked")
