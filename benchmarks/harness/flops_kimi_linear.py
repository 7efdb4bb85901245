"""Operations of the decoder family `kimi_linear_kda_q`
(Kimi-Linear-48B-A3B's blocks - Kimi Delta Attention or latent
attention over a dense SwiGLU or a routed + shared expert layer - under
the R2D2 sequence loss), from shapes. Two counts, and why they differ:

- `model_step_flops`: what the ALGORITHM needs for one train step, the
  yardstick of `learner.mfu` (registered in `harness/flops.py`'s one
  table, as flops_afmoe.py registers its family's): a forward per
  burn-in token through the online and the target net, and forward +
  backward (3x) through the online net plus a forward through the
  target net per trained token. A token's forward: a KDA mixer's four
  projections (hidden x heads x d), its two low-rank gates (hidden x d
  x heads x d each), the beta projection, three convolutions of K taps
  and THE RECURRENCE ITSELF, 7 d_k d_v a head (the decay of S, k^T S,
  the rank-one write - two -, S^T q - two: the one-position form, which
  is what the algorithm is; the chunked form's extra tile work is how
  this program runs it); an MLA mixer's four projections and, for each
  earlier key, 2 (nope + rope) + 2 v a head; the dense SwiGLU or the
  router, the shared expert and the routed experts at their EXPECTED
  load (top_k x held / all); the head over the vocabulary rows held.
  Recomputation is left out.
- `scan_floor_seconds`: the least time one v5e could take for what the
  program does under the scope `kda.scan` in one train step, the
  numerator of `kernels.kda_scan_roofline`: the recurrence's 7 d_k d_v
  FLOP a token, head and forward pass (a backward pass two forwards'
  worth) and the float32 bytes of q, k, g (d_k each), v (d_v) and beta
  read and o (d_v) written once a forward pass - a backward pass reads
  them and o's cotangent and writes five cotangents -, over every pass
  the step makes: the prefix through both nets, the trained segment
  through the target net, and through the online net forward, forward
  again (every block is recomputed) and backward; the LARGER of FLOP /
  the peak's FLOP/s and bytes / the peak's bytes/s. It is counted from
  `model_sizes` and NEVER FROM THE CHUNK SIZE, so that it reads the same
  work whatever implements the scan, and no implementation can pass
  100%: none can read less than the inputs once or skip the rule's own
  arithmetic. At d_k = d_v = 128 the bytes bound it (3.1 ns a token,
  head and forward pass against 0.58 of FLOP).
"""

from __future__ import annotations

from benchmarks.harness.flops import TRAIN_STEP_FLOPS

FAMILY = "kimi_linear_kda_q"
RULE_FLOPS = 7.0       # x d_k x d_v, a token and head: the module docstring


def _kinds(m: dict) -> tuple[int, int]:
    """-> (KDA layers, MLA layers) held."""
    kda = sum(k == "kda" for k in m["mixer_types"])
    return kda, len(m["mixer_types"]) - kda


def token_flops(m: dict) -> tuple[float, float, float]:
    """-> (a token's forward FLOP outside the MLA layers' pairs and the
    head, summed over the layers held; FLOP per causal pair of one MLA
    layer; the head's FLOP a token)."""
    h, d, heads = m["hidden_size"], m["linear_head_dim"], m["linear_num_heads"]
    width = heads * d
    kda = (2.0 * 4 * h * width + 2 * 2.0 * (h * d + d * width)
           + 2.0 * h * heads
           + 2.0 * 3 * m["linear_short_conv_kernel_size"] * width
           + RULE_FLOPS * d * d * heads)
    a, q_dim = m["num_attention_heads"], (m["qk_nope_head_dim"]
                                          + m["qk_rope_head_dim"])
    mla = 2.0 * (h * a * q_dim
                 + h * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
                 + m["kv_lora_rank"] * a * (m["qk_nope_head_dim"]
                                            + m["v_head_dim"])
                 + a * m["v_head_dim"] * h)
    pair = (2.0 * q_dim + 2.0 * m["v_head_dim"]) * a
    dense_layers = m["first_k_dense_replace"]
    moe_layers = m["num_hidden_layers"] - dense_layers
    routed_here = (m["num_experts_per_token"] * m["experts_held"]
                   / m["num_experts"])
    moe = (2.0 * h * m["num_experts"] + 6.0 * h * m["moe_intermediate_size"]
           * (m["num_shared_experts"] + routed_here))
    n_kda, n_mla = _kinds(m)
    rest = (n_kda * kda + n_mla * mla
            + dense_layers * 6.0 * h * m["intermediate_size"]
            + moe_layers * moe)
    return rest, pair, 2.0 * h * m["vocab_held"]


def model_step_flops(sizes: dict, m: dict) -> float:
    """FLOP per train step (see the module docstring)."""
    rest, pair, head = token_flops(m)
    length, burn = m["seq_length"], m["burn_in"]
    trained = length - burn
    n_mla = _kinds(m)[1]
    pairs_burn = n_mla * burn * (burn + 1) // 2
    pairs_trained = n_mla * (burn * trained + trained * (trained + 1) // 2)
    per_sequence = (
        2.0 * (burn * (rest + head) + pair * pairs_burn)
        + 4.0 * (trained * (rest + head) + pair * pairs_trained))
    return sizes["batch_size"] * per_sequence


def scan_work(batch_size: int, m: dict) -> tuple[float, float]:
    """-> (FLOP, bytes) of the delta rule per train step, every KDA
    layer held (the module docstring's `scan_floor_seconds`)."""
    d, heads = m["linear_head_dim"], m["linear_num_heads"]
    burn, trained = m["burn_in"], m["seq_length"] - m["burn_in"]
    per = batch_size * heads * _kinds(m)[0]         # a position's heads
    forward_passes = per * (2 * burn + 3 * trained)
    backward_passes = per * trained
    flops = RULE_FLOPS * d * d * (forward_passes + 2 * backward_passes)
    read = 4.0 * (3 * d + d + 1)                    # q, k, g; v; beta
    forward_bytes = read + 4.0 * d                  # ... and o written
    backward_bytes = read + 4.0 * d + read          # ... o's ct; five cts
    return flops, (forward_passes * forward_bytes
                   + backward_passes * backward_bytes)


def scan_floor_seconds(batch_size: int, m: dict, peak) -> float:
    flops, moved = scan_work(batch_size, m)
    return max(flops / peak.bf16_flops_per_s, moved / peak.hbm_bytes_per_s)


def register(model_sizes: dict) -> None:
    """Put the family in the table, bound to `model_sizes` (the reader
    passes `sizes` alone)."""
    TRAIN_STEP_FLOPS[FAMILY] = lambda sizes: model_step_flops(
        sizes, model_sizes)
