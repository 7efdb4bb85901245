"""Operations of the decoder family `ouro_looped_q` (Ouro-2.6B's blocks
- full attention and a dense SwiGLU MLP, the whole stack run
`total_ut_steps` times with the same weights - under the R2D2 sequence
loss), from shapes. Two counts, and why they differ:

- `model_step_flops`: what the ALGORITHM needs for one train step, the
  yardstick of `learner.mfu` (registered in `harness/flops.py`'s one
  table, as flops_afmoe.py registers its family's): a forward per
  burn-in token through the online and the target net, and forward +
  backward (3x) through the online net plus a forward through the
  target net per trained token. EVERY LAYER IS COUNTED ONCE PER LOOP
  STEP: a looped weight is applied `total_ut_steps` times a token and
  each application is work the loss asks for. A token's forward through
  one block application: four projections (q, k, v, o: hidden x heads x
  head_dim each, ungrouped) and the MLP's three matrices; a query pays
  4 x head_dim x heads for each earlier key (every layer is a full
  one); the head over the whole vocabulary once a token. Recomputation
  is left out (it is how this program fits the chip, not work the loss
  asks for).
- `executed_dense_ffn_flops`: what the PROGRAM executes under the scope
  `ouro.mlp` per train step, the numerator of
  `kernels.dense_ffn_mm_roofline`: 6 x rows x hidden x intermediate per
  forward application, over every pass the step makes - the burn-in
  prefix through both nets, the trained segment through the target net,
  and through the online net forward, forward again (every block
  application is recomputed in the backward pass) and backward (two
  forwards' worth: one matmul for the input's cotangent, one for the
  weight's, per matrix) - times the block applications of a forward,
  loop steps x layers. A roofline share divides executed work by the
  time it took, so it counts the recomputation and the backward pass
  the time includes.

The attention's executed count is the accepted reader's own
(`flops_afmoe.executed_attention_flops`, `kernels.attn_flash_roofline`)
and takes every size from the configuration file's `model_sizes`, which
names one full layer per block APPLICATION under the key names that
function reads (`layer_types`: steps x layers entries; a benchmark test
holds each repeated key to the model's own).
"""

from __future__ import annotations

from benchmarks.harness.flops import TRAIN_STEP_FLOPS

FAMILY = "ouro_looped_q"


def applications(m: dict) -> int:
    """Block applications of one forward pass: loop steps x layers."""
    return m["total_ut_steps"] * m["num_hidden_layers"]


def token_flops(m: dict) -> tuple[float, float, float]:
    """-> (a token's forward FLOP through ONE block application outside
    the attention's pairs; FLOP per query-key pair of one application;
    the head's FLOP a token)."""
    h = m["hidden_size"]
    q_out = m["num_attention_heads"] * m["head_dim"]
    kv_out = m["num_key_value_heads"] * m["head_dim"]
    projections = 2.0 * (2 * h * q_out + 2 * h * kv_out)
    mlp = 6.0 * h * m["intermediate_size"]
    pair = 4.0 * m["head_dim"] * m["num_attention_heads"]
    return projections + mlp, pair, 2.0 * h * m["vocab_size"]


def causal_pairs(first: int, count: int) -> int:
    """Query-key pairs of the queries at positions first .. first +
    count - 1 of a causal sequence (a query sees itself)."""
    return sum(range(first + 1, first + count + 1))


def model_step_flops(sizes: dict, m: dict) -> float:
    """FLOP per train step (see the module docstring). At the published
    widths, 6 layers x 4 steps, batch 1 x (1,024 + 3,072): 56.4
    TFLOP."""
    block, pair, head = token_flops(m)
    n = applications(m)
    length, burn = m["seq_length"], m["burn_in"]
    per_sequence = (
        2.0 * (burn * (n * block + head) + n * pair * causal_pairs(0, burn))
        + 4.0 * ((length - burn) * (n * block + head)
                 + n * pair * causal_pairs(burn, length - burn)))
    return sizes["batch_size"] * per_sequence


def executed_dense_ffn_flops(batch_size: int, m: dict) -> float:
    """FLOP executed under `ouro.mlp` per train step."""
    length, burn = m["seq_length"], m["burn_in"]
    forwards = 2.0 * burn + 5.0 * (length - burn)   # rows x passes
    return (batch_size * applications(m) * forwards
            * 6.0 * m["hidden_size"] * m["intermediate_size"])


def register(model_sizes: dict) -> None:
    """Put the family in the table, bound to `model_sizes` (the reader
    passes `sizes` alone)."""
    TRAIN_STEP_FLOPS[FAMILY] = lambda sizes: model_step_flops(
        sizes, model_sizes)
