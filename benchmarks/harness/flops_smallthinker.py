"""Operations of the decoder family `smallthinker_swa_q`
(SmallThinker-21BA3B-Instruct's blocks under the R2D2 sequence loss),
from shapes: the family's MODEL count, the yardstick of `learner.mfu`,
registered in `harness/flops.py`'s one table as flops_afmoe.py
registers its family's.

`model_step_flops`: what the ALGORITHM needs for one train step - a
forward per burn-in token through the online and the target net, and
forward + backward (3x) through the online net plus a forward through
the target net per trained token. A token's forward outside the
attention's pairs: four projections (q, o: hidden x heads x head_dim;
k, v: hidden x kv heads x head_dim - no output gate, which is where
this count parts from flops_afmoe's), the router (hidden x all 64
experts, once a layer, wherever it reads), the routed experts at their
EXPECTED load (top_k x held / total assignments a token, three
matrices; no shared expert, no dense layer), and the head over the
vocabulary rows held. A query pays 4 x head_dim x heads for each key
its mask admits (`flops_afmoe.admitted_pairs`: a sliding layer's at
most the window, a global layer's every earlier one).

The two EXECUTED counts are the accepted readers' own and need nothing
from here: `flops_afmoe.executed_attention_flops`
(`kernels.attn_flash_roofline`) and
`flops_glm_moe.executed_expert_flops`
(`kernels.moe_expert_mm_roofline`) take every size from the
configuration file's `model_sizes`, which repeats this model's keys
under the names those functions read (`moe_intermediate_size`,
`layer_types`, `sliding_window`, `num_experts`, ...; a benchmark test
holds each to the key it repeats). Why model and executed counts
differ: the executed ones count what the program does in the time the
kernel's scope measures - every block's recomputation in the backward
pass, the backward pass's five tile matmuls, the rows the step's
counters say were routed here - and a roofline share divides by that
time; the model count leaves recomputation out (it is how this program
fits the chip, not work the loss asks for) and takes the experts at
their expected load, because `learner.mfu` divides by the whole step.
"""

from __future__ import annotations

from benchmarks.harness.flops import TRAIN_STEP_FLOPS
from benchmarks.harness.flops_afmoe import _pairs

FAMILY = "smallthinker_swa_q"


def token_flops(m: dict) -> tuple[float, float, float]:
    """-> (a token's forward FLOP outside the attention's pairs and the
    head, summed over the layers held; FLOP per admitted pair of one
    layer; the head's FLOP a token)."""
    h = m["hidden_size"]
    q_out = m["num_attention_heads"] * m["head_dim"]
    kv_out = m["num_key_value_heads"] * m["head_dim"]
    projections = 2.0 * (2 * h * q_out + 2 * h * kv_out)
    router = 2.0 * h * m["moe_num_primary_experts"]
    routed_here = (m["moe_num_active_primary_experts"] * m["experts_held"]
                   / m["moe_num_primary_experts"])
    experts = 6.0 * h * m["moe_ffn_hidden_size"] * routed_here
    rest = m["num_hidden_layers"] * (projections + router + experts)
    pair = 4.0 * m["head_dim"] * m["num_attention_heads"]
    return rest, pair, 2.0 * h * m["vocab_held"]


def model_step_flops(sizes: dict, m: dict) -> float:
    """FLOP per train step (see the module docstring). At the published
    widths, 4 layers, batch 1 x (4,096 + 12,288): 34.13 TFLOP."""
    rest, pair, head = token_flops(m)
    length, burn = m["seq_length"], m["burn_in"]
    pairs = _pairs(m)
    per_sequence = (
        2.0 * (burn * (rest + head) + pair * pairs["burn"])
        + 4.0 * ((length - burn) * (rest + head)
                 + pair * (pairs["cached"] + pairs["new"])))
    return sizes["batch_size"] * per_sequence


def register(model_sizes: dict) -> None:
    """Put the family in the table, bound to `model_sizes` (the reader
    passes `sizes` alone)."""
    TRAIN_STEP_FLOPS[FAMILY] = lambda sizes: model_step_flops(
        sizes, model_sizes)
