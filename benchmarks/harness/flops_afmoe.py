"""Operations of the decoder family `afmoe_swa_q` (Trinity-Mini's blocks
under the R2D2 sequence loss), from shapes. Two counts, and why they
differ:

- `model_step_flops`: what the ALGORITHM needs for one train step, the
  yardstick of `learner.mfu` (registered in `harness/flops.py`'s one
  table, as flops_glm_moe.py registers its family): a forward per
  burn-in token through the online and the target net, and forward +
  backward (3x) through the online net plus a forward through the
  target net per trained token. Recomputation is left out (it is how
  this program fits the chip, not work the loss asks for), a query pays
  for the keys its mask admits (a sliding layer's at most
  `sliding_window`, a full layer's every earlier one - not the masked
  square, and not the whole 512 x 512 tiles the program computes along
  the mask's edges), and the routed experts take their EXPECTED load:
  top_k x held / total assignments a token.
- `executed_attention_flops`: what the PROGRAM executes inside
  ops/blockwise_attention.py per train step, the numerator of
  `kernels.attn_flash_roofline`: 4 x head_dim x heads per admitted
  query-key pair and forward pass (q.k and p.v), over every pass the
  step makes - the burn-in prefix through both nets, the trained
  segment through the target net, and through the online net forward,
  forward again (each block is recomputed in the backward pass) and
  backward, whose five tile matmuls (scores again, dp, dq, dk, dv) are
  2.5 forwards for a pair whose key is a trained position and 1.5 for
  one whose key is in the prefix cache (no dk, dv there). A roofline
  share divides executed work by the time it took, so it counts the
  recomputation and the backward pass the time includes; it still
  counts admitted pairs, not tiles, so the masked halves of the tiles
  along the diagonal and the window's edge read as time without work.
"""

from __future__ import annotations

from benchmarks.harness.flops import TRAIN_STEP_FLOPS

FAMILY = "afmoe_swa_q"
SLIDING = "sliding_attention"


def admitted_pairs(kind: str, first: int, count: int, window: int,
                   cached_below: int = 0) -> tuple[int, int]:
    """Queries at positions first .. first + count - 1 of a causal
    sequence -> (pairs whose key is below `cached_below`, pairs whose
    key is at or above it) that `kind`'s mask admits."""
    cached = new = 0
    for p in range(first, first + count):
        lowest = max(p - window + 1, 0) if kind == SLIDING else 0
        in_cache = max(min(cached_below, p + 1) - lowest, 0)
        cached += in_cache
        new += p + 1 - lowest - in_cache
    return cached, new


def _layer_flops(m: dict) -> tuple[float, float, float]:
    """-> (a token's FLOP outside the attention's pairs and the head,
    summed over the layers held; FLOP per admitted pair of one layer;
    the head's FLOP a token)."""
    h = m["hidden_size"]
    q_out = m["num_attention_heads"] * m["head_dim"]
    kv_out = m["num_key_value_heads"] * m["head_dim"]
    projections = 2.0 * (3 * h * q_out + 2 * h * kv_out)   # q, gate, o; k, v
    dense_layers = m["num_dense_layers"]
    moe_layers = m["num_hidden_layers"] - dense_layers
    dense = 6.0 * h * m["intermediate_size"]
    routed_here = (m["num_experts_per_tok"] * m["experts_held"]
                   / m["num_experts"])
    moe = (2.0 * h * m["num_experts"] + 6.0 * h * m["moe_intermediate_size"]
           * (m["num_shared_experts"] + routed_here))
    rest = ((dense_layers + moe_layers) * projections
            + dense_layers * dense + moe_layers * moe)
    pair = 4.0 * m["head_dim"] * m["num_attention_heads"]
    return rest, pair, 2.0 * h * m["vocab_held"]


def _pairs(m: dict) -> dict:
    """Admitted pairs of one sequence, summed over the layers held:
    `burn` (the prefix pass), `cached`/`new` (the trained pass, by
    where the key lies)."""
    length, burn, window = m["seq_length"], m["burn_in"], m["sliding_window"]
    out = {"burn": 0, "cached": 0, "new": 0}
    for kind in m["layer_types"]:
        out["burn"] += sum(admitted_pairs(kind, 0, burn, window))
        cached, new = admitted_pairs(kind, burn, length - burn, window, burn)
        out["cached"] += cached
        out["new"] += new
    return out


def model_step_flops(sizes: dict, m: dict) -> float:
    """FLOP per train step (see the module docstring). At the published
    widths, 1 + 4 layers, batch 2 x (2,048 + 6,144): 41.7 TFLOP."""
    rest, pair, head = _layer_flops(m)
    length, burn = m["seq_length"], m["burn_in"]
    pairs = _pairs(m)
    per_sequence = (
        2.0 * (burn * (rest + head) + pair * pairs["burn"])
        + 4.0 * ((length - burn) * (rest + head)
                 + pair * (pairs["cached"] + pairs["new"])))
    return sizes["batch_size"] * per_sequence


def executed_attention_flops(batch_size: int, m: dict) -> float:
    """FLOP the blockwise attention executes per train step, every
    layer held, both kinds."""
    _, pair, _ = _layer_flops(m)
    pairs = _pairs(m)
    trained = pairs["cached"] + pairs["new"]
    forwards = (2.0 * pairs["burn"]            # prefix: online, target
                + 3.0 * trained                # target; online twice
                + 2.5 * pairs["new"] + 1.5 * pairs["cached"])   # backward
    return batch_size * pair * forwards


def register(model_sizes: dict) -> None:
    """Put the family in the table, bound to `model_sizes` (the reader
    passes `sizes` alone)."""
    TRAIN_STEP_FLOPS[FAMILY] = lambda sizes: model_step_flops(
        sizes, model_sizes)
