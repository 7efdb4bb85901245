"""Share of device busy time under the scope `afmoe.attn.full`: the
blockwise attention call (ops/blockwise_attention.py) of the
full-attention layers alone - one layer of the five held -, forward,
recomputation and backward, in %, first chip. Beside
`kernels.attn_flash_roofline`'s stderr table (`afmoe.attn.sliding`) it
says what the window saved: a sliding layer admits 2,048 keys a query
where the full one admits 5,120 on average.
benchmarks/harness/afmoe_scopes.py says how a scope's time is read."""

from benchmarks.harness import afmoe_scopes


def read(facts: dict) -> float | None:
    return afmoe_scopes.share_of_busy(facts, "afmoe.attn.full")
