"""The `jax.named_scope`s that models/lfm2_moe_q.py adds to the
family's (`lfm2.embed`; `lfm2.conv` around a conv operator and inside
it `lfm2.conv.in` - W_in -, `lfm2.conv.mix` - both gates and the
filter, nothing that is a matmul -, `lfm2.conv.out` - W_out -;
`lfm2.dense_ffn`; `lfm2.head`), read out of the run's trace with
scope_stats.py's walk - kda_scopes.py's counterpart. The net's expert
layer opens `glm.moe*`, which glm_scopes.py reads, and its attention
layer `afmoe.attn` / `afmoe.attn.full`, which afmoe_scopes.py reads
for `learner.attn_share` and `learner.attn_full_share`; they and the
loss's `head.columns` are in this table too, so that the stderr line
says where a step's time went whichever readers the cell is listed
under (`kernels.attn_flash_roofline` does not list it: its count takes
every entry of `layer_types` for a layer with pairs,
benchmarks/README_conv_cell.md). Scopes nest (an op is under every
scope named in its stack), so `lfm2.conv`'s share CONTAINS its three
parts'. A program without the scopes (a parent commit, another net)
gives an empty table and the readers return nothing."""

from __future__ import annotations

import json

from benchmarks.harness import scope_stats
from benchmarks.harness.device import say

SCOPES = ("lfm2.embed", "lfm2.conv", "lfm2.conv.in", "lfm2.conv.mix",
          "lfm2.conv.out", "lfm2.dense_ffn", "lfm2.head", "head.columns",
          "afmoe.attn", "afmoe.attn.full")


def of(facts: dict) -> dict[str, int]:
    """The run's table, computed once per result line and said on
    stderr as shares of busy time."""
    if "lfm2_scope_ns" not in facts:
        path = facts["runtime"].newest_xplane()
        facts["lfm2_scope_ns"] = (scope_stats.scope_times(path, SCOPES)
                                  if path else {})
        busy = max(facts["trace"]["devices"][0]["busy_ns"], 1)
        say("lfm2_scopes_% " + json.dumps({
            s: round(100.0 * ns / busy, 2)
            for s, ns in facts["lfm2_scope_ns"].items()}))
    return facts["lfm2_scope_ns"]


def share_of_busy(facts: dict, scope: str) -> float | None:
    """Self time under `scope` / busy time, first chip, in %."""
    busy = facts["trace"]["devices"][0]["busy_ns"]
    ns = of(facts).get(scope)
    if not ns or not busy:
        return None
    return 100.0 * ns / busy
