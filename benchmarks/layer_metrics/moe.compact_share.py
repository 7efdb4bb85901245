"""Share of a step's expert-layer applications (layers x the loss's
four net applications) whose routed rows fit the layer's `capacity`
buffers (models/expert_layer.py: what the share expects x 1.5, in tiles
of 128), in %, mean over the window's dispatches; 100 is every
application on the compact path, anything less a step that paid the
full k N width somewhere under the `lax.cond`. Read from the step's own
metrics (`moe_compact_share`, runtime/family.decoder_q_family, PR 33)
through the traffic kind's `facts["moe"]["compact_share"]`; a kind
that does not carry the counter leaves nothing to read."""


def read(facts: dict) -> float | None:
    share = (facts.get("moe") or {}).get("compact_share")
    return None if share is None else 100.0 * share
