"""The system's pytree (models/ouro_q.OuroQNet) onto the plain dict of
benchmarks/reference/ouro_q.py, and the reference's `Sizes` from the
program's configuration - smallthinker_params.py's counterpart for the
decoder family's looped net, with the same functions under the same
names (the checks walk the layers through them). Matrix layouts agree
([in, out]), so this is renaming only; the exit gate has no counterpart
in the reference (it is no part of Q) and `system_gradients` gives it
the zeros the system's must equal."""

from __future__ import annotations

from benchmarks.reference import ouro_q as ref

FFN = ("gate_proj", "up_proj", "down_proj")
# the system's name -> the reference's, one layer's attention and norms
NAMES = {"input_layernorm": "attn_norm", "q_proj": "wq", "k_proj": "wk",
         "v_proj": "wv", "o_proj": "wo",
         "post_attention_layernorm": "attn_out_norm",
         "pre_mlp_layernorm": "ffn_norm",
         "post_mlp_layernorm": "ffn_out_norm"}
GATE = "early_exit_gate"


def sizes(ou) -> ref.Sizes:
    """`ou`: configs.OuroConfig as run."""
    return ref.Sizes(
        heads=ou.num_attention_heads, kv_heads=ou.num_key_value_heads,
        head_dim=ou.head_dim, rms_norm_eps=ou.rms_norm_eps,
        rope_theta=ou.rope_theta, loop_steps=ou.total_ut_steps)


def num_layers(sys_params: dict) -> int:
    return len(sys_params["layers"])


def reference_layer(sys_params: dict, index: int) -> dict:
    """Layer `index` of the system's under the reference's names; the
    arrays are the system's own."""
    p = sys_params["layers"][index]
    return {**{new: p[old] for old, new in NAMES.items()},
            "mlp": tuple(p["mlp"][k] for k in FFN)}


def reference_params(sys_params: dict) -> dict:
    return {"embed": sys_params["embed_tokens"],
            "layers": [reference_layer(sys_params, i)
                       for i in range(num_layers(sys_params))],
            "final_norm": sys_params["norm"],
            "head": sys_params["lm_head"]}


def system_layer_gradients(p: dict) -> dict:
    """One layer of the reference's gradients renamed back into the
    system's names."""
    return {**{old: p[new] for old, new in NAMES.items()},
            "mlp": dict(zip(FFN, p["mlp"]))}


def system_gradients(ref_grads: dict, gate: dict) -> dict:
    """The reference's gradients renamed back into the system's pytree,
    so the two trees compare leaf by leaf. `gate`: the system's exit
    gate parameters, for the shapes of the zeros its gradient is."""
    import jax

    return {"embed_tokens": ref_grads["embed"],
            "layers": [system_layer_gradients(p)
                       for p in ref_grads["layers"]],
            "norm": ref_grads["final_norm"],
            GATE: jax.tree.map(lambda x: 0.0 * x, gate),
            "lm_head": ref_grads["head"]}
