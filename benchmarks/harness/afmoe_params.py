"""The system's pytree (models/afmoe_q.AfmoeQNet) onto the plain dict of
benchmarks/reference/afmoe_q.py, and the reference's `Sizes` from the
program's configuration - glm_params.py's counterpart for the decoder
family's second net, with the same functions under the same names (the
checks walk the layers through them). Matrix layouts agree ([in, out];
the system stacks the held experts on a leading axis, the reference
takes them as a list), so this is renaming and slicing only."""

from __future__ import annotations

from benchmarks.reference import afmoe_q as ref

FFN = ("gate_proj", "up_proj", "down_proj")
# the system's name -> the reference's, one layer's attention and norms
NAMES = {"input_layernorm": "attn_norm", "q_proj": "wq", "k_proj": "wk",
         "v_proj": "wv", "gate_proj": "w_gate", "o_proj": "wo",
         "q_norm": "q_norm", "k_norm": "k_norm",
         "post_attention_layernorm": "attn_out_norm",
         "pre_mlp_layernorm": "ffn_norm",
         "post_mlp_layernorm": "ffn_out_norm"}


def sizes(afmoe, router_trains: bool | None = None) -> ref.Sizes:
    """`afmoe`: configs.AfmoeConfig as run; `router_trains`: the net's
    own (`AfmoeQNet.router_trains`), by default what it is without an
    exchange between the shares."""
    if not afmoe.mup_enabled:
        raise ValueError("the reference is Trinity-Mini's: mup_enabled")
    held = afmoe.num_experts // afmoe.shard_count
    return ref.Sizes(
        heads=afmoe.num_attention_heads,
        kv_heads=afmoe.num_key_value_heads, head_dim=afmoe.head_dim,
        layer_types=tuple(afmoe.layer_types), window=afmoe.sliding_window,
        top_k=afmoe.num_experts_per_tok,
        routed_scaling_factor=afmoe.route_scale,
        norm_topk_prob=afmoe.route_norm, rms_norm_eps=afmoe.rms_norm_eps,
        rope_theta=afmoe.rope_theta, first_expert=afmoe.shard_index * held,
        experts_held=held,
        router_trains=(afmoe.shard_count == 1 if router_trains is None
                       else router_trains),
        forced_balance=afmoe.force_balanced_routing)


def _layer(p: dict) -> dict:
    """One layer of the system's, under the reference's names."""
    out = {new: p[old] for old, new in NAMES.items()}
    mlp = p["mlp"]
    if "experts" not in mlp:
        out["dense"] = tuple(mlp[k] for k in FFN)
        return out
    held = mlp["experts"]["gate_proj"].shape[0]
    out["router"] = mlp["gate"]
    out["router_bias"] = mlp["e_score_correction_bias"]
    out["experts"] = [tuple(mlp["experts"][k][j] for k in FFN)
                      for j in range(held)]
    out["shared"] = tuple(mlp["shared_experts"][k] for k in FFN)
    return out


def num_layers(sys_params: dict) -> int:
    return len(sys_params["layers"])


def reference_layer(sys_params: dict, index: int) -> dict:
    """Layer `index` of the system's under the reference's names; the
    arrays are the system's own (a caller that walks the layers holds
    one layer's expert slices at a time)."""
    return _layer(sys_params["layers"][index])


def reference_params(sys_params: dict) -> dict:
    return {"embed": sys_params["embed_tokens"],
            "layers": [reference_layer(sys_params, i)
                       for i in range(num_layers(sys_params))],
            "final_norm": sys_params["norm"],
            "head": sys_params["lm_head"]}


def system_layer_gradients(p: dict) -> dict:
    """One layer of the reference's gradients renamed back into the
    system's names (the held experts stacked on a leading axis)."""
    import jax.numpy as jnp

    out = {old: p[new] for old, new in NAMES.items()}
    if "dense" in p:
        out["mlp"] = dict(zip(FFN, p["dense"]))
    else:
        out["mlp"] = {
            "gate": p["router"],
            "e_score_correction_bias": p["router_bias"],
            "experts": {k: jnp.stack([e[i] for e in p["experts"]])
                        for i, k in enumerate(FFN)},
            "shared_experts": dict(zip(FFN, p["shared"]))}
    return out


def system_gradients(ref_grads: dict) -> dict:
    """The reference's gradients renamed back into the system's pytree,
    so the two trees compare leaf by leaf."""
    return {"embed_tokens": ref_grads["embed"],
            "layers": [system_layer_gradients(p)
                       for p in ref_grads["layers"]],
            "norm": ref_grads["final_norm"], "lm_head": ref_grads["head"]}
