"""Operations and bytes of a DECODE STEP of the decoder family
`jamba_slots` (Jamba2's hybrid stack served whole from slots:
benchmarks/traffic_kinds/slot_fleet_closed_loop.py), from the
configuration file's `model_sizes` and the mix's rows and contexts as
the window found them - never from the implementation, so the shares
read the same work whatever computes it.

A step answers `rows` sessions, one new token each, session r at a
context of `contexts[r]` positions:

- `step_flops`: what the ALGORITHM needs (the numerator of
  `server.ssm_step_mfu`): per row 2 FLOP a weight of every matrix, the
  tied head over the whole vocabulary included (the embedding's lookup
  is the same matrix and costs none); per Mamba layer the conv's taps
  (2 a tap and channel) and the scan (6 a channel and state coordinate:
  the decay's product and exponential, the input's two products, the
  sum, the read); per attention layer 4 d a query head and position.
- `step_bytes`: what the step cannot avoid moving (the numerator of
  `server.ssm_step_hbm_roofline`): every matrix read ONCE a step in the
  served dtype however many rows share it; per row every Mamba layer's
  state and conv tail read and written; per row and attention layer the
  context's keys and values read once. The reply (rows x vocabulary
  float32) and the activations are left out: a floor.
- `ssm_state_bytes`: the second part alone, the numerator of
  `kernels.ssm_state_roofline`.
"""

from __future__ import annotations

# the contexts of ONE step of the window (the sessions' mean context at
# its middle, as many times as a step answered rows): one definition
from benchmarks.harness.flops_minicpm_sala import (  # noqa: F401
    window_contexts)

FAMILY = "jamba_slots"
MAMBA, ATTENTION = "mamba", "attention"
SERVED_BYTES = 2        # bfloat16 matrices, keys, values, conv tails
STATE_BYTES = 4         # float32 scan state


def _counts(m: dict) -> tuple[int, int]:
    kinds = list(m["layer_kinds"])
    return kinds.count(MAMBA), kinds.count(ATTENTION)


def matrix_params(m: dict) -> int:
    """Parameters of every matrix a decode step multiplies by: the
    mixers' projections, the MLPs and the head (= the embedding); not
    the conv's filter, A_log, the biases or the norms' gains."""
    h, di, n, r = (m["hidden_size"], m["d_inner"], m["mamba_d_state"],
                   m["mamba_dt_rank"])
    mlp = 3 * h * m["intermediate_size"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    mamba, attention = _counts(m)
    return (mamba * (h * 2 * di + di * (r + 2 * n) + r * di + di * h)
            + attention * (h * (q + 2 * kv) + q * h)
            + (mamba + attention) * mlp + h * m["vocab_size"])


def session_state_bytes(m: dict) -> int:
    """Bytes one session holds in ONE Mamba layer: the float32 state
    and the conv's tail."""
    return m["d_inner"] * (STATE_BYTES * m["mamba_d_state"]
                           + SERVED_BYTES * (m["mamba_d_conv"] - 1))


def ssm_state_bytes(m: dict, rows: float) -> float:
    """Every row's Mamba state read and written once a layer."""
    mamba, _ = _counts(m)
    return 2.0 * rows * mamba * session_state_bytes(m)


def attention_bytes(m: dict, contexts: list[float]) -> float:
    _, attention = _counts(m)
    row = m["num_key_value_heads"] * m["head_dim"] * SERVED_BYTES
    return sum(attention * 2.0 * row * c for c in contexts)


def step_bytes(m: dict, contexts: list[float]) -> float:
    return (SERVED_BYTES * matrix_params(m)
            + ssm_state_bytes(m, len(contexts))
            + attention_bytes(m, contexts))


def step_flops(m: dict, contexts: list[float]) -> float:
    mamba, attention = _counts(m)
    per_row = 2.0 * matrix_params(m) + mamba * m["d_inner"] * (
        2.0 * m["mamba_d_conv"] + 6.0 * m["mamba_d_state"])
    attended = sum(attention * m["num_attention_heads"] * m["head_dim"]
                   * 4.0 * c for c in contexts)
    return per_row * len(contexts) + attended


def model_sizes(facts: dict) -> dict | None:
    """The configuration's `model_sizes` if they are this family's."""
    m = facts["runtime"].cell.config.get("model_sizes") or {}
    return m if "layer_kinds" in m and "mamba_d_state" in m else None
