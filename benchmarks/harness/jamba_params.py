"""The system's pytree (models/jamba_q.JambaQNet) onto the plain dict of
benchmarks/reference/jamba_q.py, and the reference's `Sizes` from the
program's configuration - minicpm_sala_params.py's counterpart for the
decoder family's second net served from slots. Matrix layouts agree
([in, out]; the conv's filter [taps, channels]; `A_log` [channels,
d_state] as published), so this is renaming only."""

from __future__ import annotations

import numpy as np

from benchmarks.reference import jamba_q as ref

FFN = ("gate_proj", "up_proj", "down_proj")
# the system's name -> the reference's, one layer's mixer
MAMBA = {"in_proj": "w_in", "conv_weight": "conv_w", "conv_bias": "conv_b",
         "x_proj": "w_x", "dt_proj": "w_dt", "dt_bias": "dt_bias",
         "A_log": "a_log", "D": "d_skip", "out_proj": "w_out",
         "dt_layernorm": "dt_norm", "b_layernorm": "b_norm",
         "c_layernorm": "c_norm"}
ATTENTION = {"q_proj": "wq", "k_proj": "wk", "v_proj": "wv", "o_proj": "wo"}

# departures a check must refuse: fields of `ref.Sizes`, each with the
# value that departs (`FROM_FIRST_COMPARED`: the check puts the first
# compared position there). The Q rule refuses five of them; a carry
# rounded to bfloat16 is the STATE rule's (Q reads it 1.02-1.43 units
# of 1.4: h C is a third of y beside D x) and attention one position
# short the KEY COUNT's (one key of thousands moves no Q)
FROM_FIRST_COMPARED = "from the first compared position"
DEPARTURES = {"no_dt_bias": True, "no_inner_norms": True,
              "conv_tail_dropped": True,
              "padding_advances_from": FROM_FIRST_COMPARED, "no_skip": True,
              "carry_rounded": True, "attn_one_short": True}
# the program counter that the reference's `keys_attended` is held to
KEYS_ATTENDED = "attn_positions_read"


def sizes(jamba, **departures) -> ref.Sizes:
    """`jamba`: configs.JambaConfig as run; `departures`: fields of
    `ref.Sizes` a check wants refused."""
    return ref.Sizes(
        kinds=tuple(
            ref.ATTENTION
            if i % jamba.attn_layer_period == jamba.attn_layer_offset
            else ref.MAMBA for i in range(jamba.num_hidden_layers)),
        heads=jamba.num_attention_heads, kv_heads=jamba.num_key_value_heads,
        head_dim=jamba.hidden_size // jamba.num_attention_heads,
        d_inner=jamba.mamba_expand * jamba.hidden_size,
        d_state=jamba.mamba_d_state, dt_rank=jamba.mamba_dt_rank,
        d_conv=jamba.mamba_d_conv, rms_norm_eps=jamba.rms_norm_eps,
        **departures)


def reference_layer(sys_params: dict, index: int) -> dict:
    """Layer `index` of the system's under the reference's names; the
    arrays are the system's own."""
    p = sys_params["layers"][index]
    mixer = ({MAMBA[k]: v for k, v in p["mamba"].items()} if "mamba" in p
             else {ATTENTION[k]: v for k, v in p["self_attn"].items()})
    return {"mixer_norm": p["input_layernorm"],
            "ffn_norm": p["pre_ff_layernorm"], **mixer,
            "mlp": tuple(p["mlp"][k] for k in FFN)}


def device_state(slot_state: dict, slot: int):
    """What the server holds of session `slot`'s recurrence, under the
    reference's layout: [Mamba layers, channels, d_state] float32 (the
    system keeps d_state before channels)."""
    return np.stack([np.asarray(h[slot]).T for h in slot_state["ssm"]])


def ends(sys_params: dict) -> dict:
    """What the reference's `embed` and `head` read."""
    return {"embed": sys_params["embed_tokens"],
            "final_norm": sys_params["final_layernorm"]}


def reference_params(sys_params: dict) -> dict:
    return {**ends(sys_params),
            "layers": [reference_layer(sys_params, i)
                       for i in range(len(sys_params["layers"]))]}
