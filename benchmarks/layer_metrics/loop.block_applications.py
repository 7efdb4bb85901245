"""Blocks applied per net forward pass (loop steps x layers), counted
where they are applied: the step's own counter
`loop_block_applications` (runtime/family.decoder_q_family, for a net
without an expert layer: models/ouro_q.py adds one to the scan's carry
at every block application), mean over the window's dispatches, read
through the traffic kind's `facts["loop"]["block_applications"]`. It
has to read `total_ut_steps` x `num_hidden_layers` of the
configuration (24 in `ouro_offline`): fewer is a loop that stopped
early or a layer that was skipped. A kind that does not carry the
counter leaves nothing to read."""


def read(facts: dict) -> float | None:
    return (facts.get("loop") or {}).get("block_applications")
