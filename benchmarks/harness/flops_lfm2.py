"""Operations of the decoder family `lfm2_moe_q` (LFM2-24B-A2B's blocks
- a gated short convolution or grouped-query attention over a dense
SwiGLU or a routed expert layer, a head that is the embedding - under
the R2D2 sequence loss), from shapes. Two counts, and why they differ:

- `model_step_flops`: what the ALGORITHM needs for one train step, the
  yardstick of `learner.mfu` (registered in `harness/flops.py`'s one
  table, as flops_afmoe.py registers its family's): a forward per
  burn-in token through the online and the target net, and forward +
  backward (3x) through the online net plus a forward through the
  target net per trained token. A token's forward: a conv operator's
  two projections (hidden x 3 hidden, hidden x hidden) and THE MIXER
  ITSELF, (2 K + 2) hidden (the gate B * x~, K multiply-adds of the
  filter, the gate C * c); an attention operator's four projections
  and, for each earlier key, 4 d a head; the dense SwiGLU or the router
  and the routed experts at their EXPECTED load (top_k x held / all; no
  shared expert); THE HEAD AS IT RUNS, on the trained tokens alone (the
  loss reads no Q of the prefix): where the loss reads by column
  (`head_by_column`) ONE product over the vocabulary held a token - the
  online net's slice for the argmax, without gradient - and two column
  reads of 2 hidden (the target's, and the online net's with its
  backward pass: four forwards' worth in all); else the dense read's
  four products. Recomputation is left out.
- `conv_floor_seconds`: the least time one v5e could take for what the
  program does under the scope `lfm2.conv` - THE WHOLE OPERATOR, both
  projections, both gates and the filter - in one train step, the
  numerator of `kernels.short_conv_roofline`. A token's forward FLOP in
  one conv layer (the two projections' 2 x 4 hidden^2 and the mixer's
  (2 K + 2) hidden; a backward pass two forwards' worth) and the bytes
  NO implementation can avoid - the operator's input read and its
  output written once a forward pass in the dtype the configuration
  states; a backward pass reads the input and the output's cotangent
  and writes the input's; the weights left out, which only lowers the
  floor -, over every pass the step makes: the prefix through both
  nets, the trained segment through the target net, and through the
  online net forward, forward again (every block is recomputed) and
  backward; the LARGER of FLOP / the peak's FLOP/s and bytes / the
  peak's bytes/s. It is counted from `model_sizes` and NEVER FROM HOW
  THE PROGRAM FUSES IT, and over the scope that cannot lose the work:
  XLA moves B * x~ into W_in's fusion and C * c into W_out's, and a
  fusion has one name, so no scope's time is the gates' and filter's
  alone and a floor of theirs over the time under `lfm2.conv.mix`
  would pass 100% the day the mixer is fused whole. This one cannot:
  every product is counted once at the MXU's peak and an operator
  fused into one kernel still makes them. At hidden 2,048 the FLOP
  bound it (170 ns a token and forward pass against 10 of bytes), so
  what the share leaves under 100% is the projections' distance from
  the peak PLUS all of the mixer's time.
"""

from __future__ import annotations

from benchmarks.harness.flops import TRAIN_STEP_FLOPS

FAMILY = "lfm2_moe_q"
CONV = "conv"
ITEMSIZE = {"bfloat16": 2, "float32": 4}


def _kinds(m: dict) -> tuple[int, int]:
    """-> (conv layers, attention layers) held."""
    conv = sum(k == CONV for k in m["layer_types"])
    return conv, len(m["layer_types"]) - conv


def mix_flops(m: dict) -> float:
    """The mixer's own FLOP a token and forward pass, one conv layer."""
    return (2.0 * m["conv_L_cache"] + 2.0) * m["hidden_size"]


def conv_flops(m: dict) -> float:
    """One conv operator's FLOP a token and forward pass: W_in, the
    mixer, W_out."""
    h = m["hidden_size"]
    return 2.0 * (h * 3 * h + h * h) + mix_flops(m)


def token_flops(m: dict) -> tuple[float, float, float, float]:
    """-> (a token's forward FLOP outside the attention's pairs and the
    head, summed over the layers held; FLOP per causal pair of one
    attention layer; one head product's FLOP a token; one column
    read's)."""
    h, d = m["hidden_size"], m["head_dim"]
    q_out = m["num_attention_heads"] * d
    kv_out = m["num_key_value_heads"] * d
    conv = conv_flops(m)
    attention = 2.0 * (2 * h * q_out + 2 * h * kv_out)     # q, o; k, v
    pair = 4.0 * d * m["num_attention_heads"]
    dense_layers = m["num_dense_layers"]
    moe_layers = m["num_hidden_layers"] - dense_layers
    routed_here = (m["num_experts_per_tok"] * m["experts_held"]
                   / m["num_experts"])
    moe = (2.0 * h * m["num_experts"]
           + 6.0 * h * m["moe_intermediate_size"] * routed_here)
    n_conv, n_attention = _kinds(m)
    rest = (n_conv * conv + n_attention * attention
            + dense_layers * 6.0 * h * m["intermediate_size"]
            + moe_layers * moe)
    return rest, pair, 2.0 * h * m["vocab_held"], 2.0 * h


def model_step_flops(sizes: dict, m: dict) -> float:
    """FLOP per train step (see the module docstring)."""
    rest, pair, product, column = token_flops(m)
    length, burn = m["seq_length"], m["burn_in"]
    trained = length - burn
    n_attention = _kinds(m)[1]
    pairs_burn = n_attention * burn * (burn + 1) // 2
    pairs_trained = n_attention * (burn * trained
                                   + trained * (trained + 1) // 2)
    head = (product + 4.0 * column if m["head_by_column"]
            else 4.0 * product)
    per_sequence = (
        2.0 * (burn * rest + pair * pairs_burn)
        + 4.0 * (trained * rest + pair * pairs_trained)
        + trained * head)
    return sizes["batch_size"] * per_sequence


def conv_work(sizes: dict, m: dict) -> tuple[float, float]:
    """-> (FLOP, bytes) of the conv operators per train step, every
    conv layer held (the module docstring's `conv_floor_seconds`)."""
    h = m["hidden_size"]
    item = ITEMSIZE[sizes["compute_dtype"]]
    burn, trained = m["burn_in"], m["seq_length"] - m["burn_in"]
    per = sizes["batch_size"] * _kinds(m)[0]          # a position's layers
    forward_passes = per * (2 * burn + 3 * trained)
    backward_passes = per * trained
    flops = conv_flops(m) * (forward_passes + 2 * backward_passes)
    forward_bytes = item * 2 * h                      # u in, the output out
    backward_bytes = item * 3 * h                     # u, its ct in; d u out
    return flops, (forward_passes * forward_bytes
                   + backward_passes * backward_bytes)


def conv_floor_seconds(sizes: dict, m: dict, peak) -> float:
    flops, moved = conv_work(sizes, m)
    return max(flops / peak.bf16_flops_per_s, moved / peak.hbm_bytes_per_s)


def register(model_sizes: dict) -> None:
    """Put the family in the table, bound to `model_sizes` (the reader
    passes `sizes` alone)."""
    TRAIN_STEP_FLOPS[FAMILY] = lambda sizes: model_step_flops(
        sizes, model_sizes)
