"""Positions that passed a conv operator per forward pass of the online
net (prefix and trained steps), summed over the conv layers where the
operator runs: the step's counter `conv_positions`
(runtime/family.decoder_q_family, for a net with conv layers), mean
over the window's dispatches, read through the traffic kind's
`facts["conv"]["positions_mixed"]`. It has to read conv layers x
(burn-in + trained positions) x batch (4 x 16,384 x 2 = 131,072 in
`lfm2_moe_offline`): fewer is a layer or a stretch of the sequence that
was skipped. A kind that does not carry the counter leaves nothing to
read."""


def read(facts: dict) -> float | None:
    return (facts.get("conv") or {}).get("positions_mixed")
