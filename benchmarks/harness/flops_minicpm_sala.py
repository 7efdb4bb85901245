"""Operations and bytes of a DECODE STEP of the decoder family
`minicpm_sala_slots` (MiniCPM-SALA's hybrid stack served from slots:
benchmarks/traffic_kinds/slot_sessions_closed_loop.py), from the
configuration file's `model_sizes` and the mix's lengths as the window
found them - never from the implementation, so the shares read the same
work whatever computes it.

A step answers `rows` sessions, one new token each, session r at a
context of `contexts[r]` positions:

- `step_flops`: what the ALGORITHM needs (the numerator of
  `server.step_mfu`): per row 2 FLOP a weight of every matrix but the
  embedding (a lookup), the head over the whole vocabulary included;
  per lightning layer and head 5 d^2 (decay, outer product, sum, read);
  per sparse layer the scores of the visible compressed keys (2 d a
  query head and key) and the attended positions (4 d a query head and
  position: `topk` blocks once a context has passed `dense_len`, every
  position before).
- `step_bytes`: what the step cannot avoid moving (the numerator of
  `server.step_hbm_roofline`): every matrix but the embedding read ONCE
  a step in the served dtype however many rows share it; per row each
  lightning matrix read and written (float32); per row and sparse layer
  the attended blocks' keys and values and the visible compressed keys
  read once. The reply (rows x vocabulary float32) and the activations
  are left out: a floor.
- `sparse_bytes`, `lightning_bytes`: those two parts alone, the
  numerators of `kernels.sparse_decode_roofline` and
  `kernels.lightning_state_roofline`.
"""

from __future__ import annotations

FAMILY = "minicpm_sala_slots"
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
SERVED_BYTES = 2        # bfloat16 parameters, keys and values
STATE_BYTES = 4         # float32 lightning matrices


def matrix_params(m: dict) -> int:
    """Parameters of every matrix a decode step multiplies by: the
    layers' projections, gates and MLPs and the head; not the embedding,
    not the norms' gains."""
    h = m["hidden_size"]
    mlp = 3 * h * m["intermediate_size"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    lq = m["lightning_nh"] * m["lightning_head_dim"]
    per = {SPARSE: h * (3 * q + 2 * kv) + mlp, LIGHTNING: 5 * h * lq + mlp}
    return sum(per[k] for k in m["mixer_types"]) + h * m["vocab_size"]


def attended_positions(m: dict, context: float) -> float:
    """Positions a sparse layer's query attends at `context`."""
    if context <= m["sparse_dense_len"]:
        return context
    return min(m["sparse_topk"] * m["sparse_block_size"], context)


def visible_windows(m: dict, context: float) -> float:
    if context <= m["sparse_dense_len"]:
        return 0.0
    return max((context - m["sparse_kernel_size"])
               // m["sparse_kernel_stride"] + 1, 0)


def _counts(m: dict) -> tuple[int, int]:
    kinds = list(m["mixer_types"])
    return kinds.count(SPARSE), kinds.count(LIGHTNING)


def step_flops(m: dict, contexts: list[float]) -> float:
    sparse, lightning = _counts(m)
    d, heads = m["head_dim"], m["num_attention_heads"]
    per_row = 2.0 * matrix_params(m) + lightning * (
        5.0 * m["lightning_nh"] * m["lightning_head_dim"] ** 2)
    attention = sum(
        sparse * heads * d * (2.0 * visible_windows(m, c)
                              + 4.0 * attended_positions(m, c))
        for c in contexts)
    return per_row * len(contexts) + attention


def lightning_bytes(m: dict, rows: float) -> float:
    _, lightning = _counts(m)
    return (2.0 * STATE_BYTES * rows * lightning * m["lightning_nh"]
            * m["lightning_head_dim"] ** 2)


def sparse_bytes(m: dict, contexts: list[float]) -> float:
    sparse, _ = _counts(m)
    row = m["num_key_value_heads"] * m["head_dim"] * SERVED_BYTES
    return sum(sparse * row * (2.0 * attended_positions(m, c)
                               + visible_windows(m, c)) for c in contexts)


def step_bytes(m: dict, contexts: list[float]) -> float:
    return (SERVED_BYTES * matrix_params(m)
            + lightning_bytes(m, len(contexts)) + sparse_bytes(m, contexts))


def window_contexts(facts: dict) -> list[float] | None:
    """The contexts of ONE step of the window: the sessions' contexts at
    its middle, as many of them as a step answered on average (a step
    answers whole clients, so any `rows` sessions stand for it: the
    mean over all of them, `rows` times)."""
    decode = facts.get("decode")
    if not decode or not decode.get("contexts"):
        return None
    contexts = decode["contexts"]
    mean = sum(contexts) / len(contexts)
    return [mean] * max(int(round(decode["rows_per_step"])), 1)
