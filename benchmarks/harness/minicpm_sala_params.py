"""The system's pytree (models/minicpm_sala_q.MiniCpmSalaQNet) onto the
plain dict of benchmarks/reference/minicpm_sala_q.py, and the
reference's `Sizes` from the program's configuration - ouro_params.py's
counterpart for the decoder family's hybrid net. Matrix layouts agree
([in, out]), so this is renaming only."""

from __future__ import annotations

import math

from benchmarks.reference import minicpm_sala_q as ref

FFN = ("gate_proj", "up_proj", "down_proj")
# the system's name -> the reference's, one layer's mixer
MIXER = {"q_proj": "wq", "k_proj": "wk", "v_proj": "wv", "o_gate": "w_gate",
         "o_proj": "wo", "q_norm": "q_norm", "k_norm": "k_norm",
         "o_norm": "o_norm"}


# departures a check must refuse (fields of `ref.Sizes`): whether the
# departed reference chooses its blocks for itself - a departure of the
# selection is seen in the selection
DEPARTURES = {"dense_always": False, "decay_one": False,
              "forced_blocks_dropped": True, "stale_compressed": True}


def sizes(sala, **departures) -> ref.Sizes:
    """`sala`: configs.MiniCpmSalaConfig as run; `departures`: fields of
    `ref.Sizes` a check wants refused."""
    return ref.Sizes(
        mixer_types=tuple(sala.mixer_types),
        heads=sala.num_attention_heads, kv_heads=sala.num_key_value_heads,
        head_dim=sala.head_dim, lightning_heads=sala.lightning_nh,
        lightning_head_dim=sala.lightning_head_dim,
        rms_norm_eps=sala.rms_norm_eps, rope_theta=sala.rope_theta,
        scale_emb=sala.scale_emb,
        residual_scale=sala.scale_depth / math.sqrt(sala.depth_scale_layers),
        head_divisor=sala.hidden_size / sala.dim_model_base,
        block=sala.sparse_block_size, kernel=sala.sparse_kernel_size,
        stride=sala.sparse_kernel_stride,
        init_blocks=sala.sparse_init_blocks, window=sala.sparse_window_size,
        topk=sala.sparse_topk, dense_len=sala.sparse_dense_len,
        **departures)


def num_layers(sys_params: dict) -> int:
    return len(sys_params["layers"])


def reference_layer(sys_params: dict, index: int) -> dict:
    """Layer `index` of the system's under the reference's names; the
    arrays are the system's own."""
    p = sys_params["layers"][index]
    return {"attn_norm": p["input_layernorm"],
            "ffn_norm": p["post_attention_layernorm"],
            **{MIXER[k]: v for k, v in p["self_attn"].items()},
            "mlp": tuple(p["mlp"][k] for k in FFN)}


def ends(sys_params: dict) -> dict:
    """What the reference's `embed` and `head` read."""
    return {"embed": sys_params["embed_tokens"],
            "final_norm": sys_params["norm"], "head": sys_params["lm_head"]}


def reference_params(sys_params: dict) -> dict:
    return {**ends(sys_params),
            "layers": [reference_layer(sys_params, i)
                       for i in range(num_layers(sys_params))]}
