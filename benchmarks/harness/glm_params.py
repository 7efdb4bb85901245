"""The system's pytree (models/glm_moe_q.GlmMoeQNet, HF's names) onto
the plain dict of benchmarks/reference/glm_moe_q.py, and the
reference's `Sizes` from the program's configuration — the counterpart
of `r2d2_params.py` for the decoder family. Matrix layouts agree
([in, out]; the system stacks the held experts on a leading axis, the
reference takes them as a list), so this is renaming and slicing
only. On the chip the comparison walks the layers (`reference_layer`)
and holds one layer's expert slices at a time beside the learner's
state."""

from __future__ import annotations

from benchmarks.reference import glm_moe_q as ref

FFN = ("gate_proj", "up_proj", "down_proj")


def sizes(glm, router_trains: bool | None = None) -> ref.Sizes:
    """`glm`: configs.GlmMoeConfig as run; `router_trains`: the net's
    own (`GlmMoeQNet.router_trains`), by default what it is without an
    exchange between the shares."""
    held = glm.n_routed_experts // glm.shard_count
    return ref.Sizes(
        heads=glm.num_attention_heads, kv_lora_rank=glm.kv_lora_rank,
        qk_nope_head_dim=glm.qk_nope_head_dim,
        qk_rope_head_dim=glm.qk_rope_head_dim, v_head_dim=glm.v_head_dim,
        top_k=glm.num_experts_per_tok,
        routed_scaling_factor=glm.routed_scaling_factor,
        norm_topk_prob=glm.norm_topk_prob, rms_norm_eps=glm.rms_norm_eps,
        rope_theta=glm.rope_theta, first_expert=glm.shard_index * held,
        experts_held=held,
        router_trains=(glm.shard_count == 1 if router_trains is None
                       else router_trains),
        forced_balance=glm.force_balanced_routing)


def _layer(p: dict) -> dict:
    """One layer of the system's, under the reference's names."""
    out = {"attn_norm": p["input_layernorm"], "wq_a": p["q_a_proj"],
           "q_norm": p["q_a_layernorm"], "wq_b": p["q_b_proj"],
           "wkv_a": p["kv_a_proj_with_mqa"], "kv_norm": p["kv_a_layernorm"],
           "wkv_b": p["kv_b_proj"], "wo": p["o_proj"],
           "ffn_norm": p["post_attention_layernorm"]}
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
    arrays are the system's own (an expert's matrices are slices of
    the layer's stack, copied when taken on a device: a caller that
    walks the layers holds one layer's at a time)."""
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

    out = {"input_layernorm": p["attn_norm"], "q_a_proj": p["wq_a"],
           "q_a_layernorm": p["q_norm"], "q_b_proj": p["wq_b"],
           "kv_a_proj_with_mqa": p["wkv_a"],
           "kv_a_layernorm": p["kv_norm"], "kv_b_proj": p["wkv_b"],
           "o_proj": p["wo"], "post_attention_layernorm": p["ffn_norm"]}
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
