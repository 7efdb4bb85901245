"""Operations of the decoder family `glm_moe_mla_q` (GLM-4.7-Flash's
blocks under the R2D2 sequence loss), from shapes. Two counts, and why
they differ:

- `model_step_flops`: what the ALGORITHM needs for one train step, the
  yardstick of `learner.mfu` (registered in `harness/flops.py`'s one
  table the way flops_r2d2.py registers its family): a forward per
  burn-in token through the online and the target net, and forward +
  backward (3x) through the online net plus a forward through the
  target net per trained token. Recomputation is left out (it is how
  this program fits the chip, not work the loss asks for), a causal
  query pays for the keys it may see (not the masked square), the
  latent up-projection is counted once per position and pass, and the
  routed experts take their EXPECTED load: top_k x held / total
  assignments a token (T/2 rows a layer at 4 of 64 with 8 held).
- `executed_expert_flops`: what the PROGRAM executes under the scope
  `glm.moe.experts`, the numerator of `kernels.moe_expert_mm_roofline`:
  6 x rows x hidden x width per forward pass over the rows the step's
  counters say were routed here (`moe_rows`: all four net applications,
  one forward each; `moe_rows_grad`: the online net's trained rows,
  which pay a recomputed forward and a backward of two more). A
  roofline share divides executed work by the time it took, so it must
  count the recomputation the time includes and the rows that were
  really there, or it would read low by the first and wander with the
  routing by the second.
"""

from __future__ import annotations

from benchmarks.harness.flops import TRAIN_STEP_FLOPS

FAMILY = "glm_moe_mla_q"


def token_forward_flops(m: dict, keys_seen: float) -> float:
    """FLOP of one token's forward through every block held and the
    head, attending to `keys_seen` keys. `m` is the configuration
    file's `model_sizes`."""
    h, heads = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    kv_up = m["qk_nope_head_dim"] + m["v_head_dim"]
    mla = 2.0 * (h * m["q_lora_rank"] + m["q_lora_rank"] * heads * qk
                 + h * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
                 + m["kv_lora_rank"] * heads * kv_up
                 + heads * m["v_head_dim"] * h)
    mla += 2.0 * heads * (qk + m["v_head_dim"]) * keys_seen
    dense_layers = m["first_k_dense_replace"]
    moe_layers = m["num_hidden_layers"] - dense_layers
    dense = 6.0 * h * m["intermediate_size"]
    expert = 6.0 * h * m["moe_intermediate_size"]
    routed_here = (m["num_experts_per_tok"] * m["experts_held"]
                   / m["n_routed_experts"])
    moe = (2.0 * h * m["n_routed_experts"]
           + expert * (m["n_shared_experts"] + routed_here))
    head = 2.0 * h * m["vocab_held"]
    return ((dense_layers + moe_layers) * mla + dense_layers * dense
            + moe_layers * moe + head)


def model_step_flops(sizes: dict, m: dict) -> float:
    """FLOP per train step (see the module docstring). At the published
    widths, 1 + 4 layers, batch 16 x (128 + 384): 16.23 TFLOP."""
    length, burn = m["seq_length"], m["burn_in"]
    # mean keys a causal query sees: positions 0..burn-1, burn..L-1
    keys_burn = (burn + 1) / 2.0
    keys_train = (burn + length + 1) / 2.0
    per_sequence = (2.0 * burn * token_forward_flops(m, keys_burn)
                    + 4.0 * (length - burn)
                    * token_forward_flops(m, keys_train))
    return sizes["batch_size"] * per_sequence


def executed_expert_flops(moe_rows: float, moe_rows_grad: float,
                          m: dict) -> float:
    """FLOP the three grouped matmuls execute per train step."""
    per_row_forward = 6.0 * m["hidden_size"] * m["moe_intermediate_size"]
    return per_row_forward * (moe_rows + 3.0 * moe_rows_grad)


def register(model_sizes: dict) -> None:
    """Put the family in the table, bound to `model_sizes` (the reader
    passes `sizes` alone)."""
    TRAIN_STEP_FLOPS[FAMILY] = lambda sizes: model_step_flops(
        sizes, model_sizes)
