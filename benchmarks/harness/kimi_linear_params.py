"""The system's pytree (models/kimi_linear_q.KimiLinearQNet) onto the
plain dict of benchmarks/reference/kimi_linear_q.py, and the
reference's `Sizes` from the program's configuration - glm_params.py's
counterpart for the decoder family's fifth net, with the same functions
under the same names (the checks walk the layers through them). Matrix
layouts agree ([in, out]; the system stacks the held experts on a
leading axis, the reference takes them as a list), so this is renaming
and slicing only."""

from __future__ import annotations

from benchmarks.reference import kimi_linear_q as ref

FFN = ("gate_proj", "up_proj", "down_proj")
# the system's name -> the reference's, by kind of mixer
NORMS = {"input_layernorm": "attn_norm",
         "post_attention_layernorm": "ffn_norm"}
KDA = {"q_proj": "wq", "k_proj": "wk", "v_proj": "wv",
       "q_conv1d": "conv_q", "k_conv1d": "conv_k", "v_conv1d": "conv_v",
       "A_log": "a_log", "dt_bias": "dt_bias", "f_a_proj": "wf_a",
       "f_b_proj": "wf_b", "b_proj": "w_beta", "g_a_proj": "wg_a",
       "g_b_proj": "wg_b", "o_norm": "o_norm", "o_proj": "wo"}
MLA = {"q_proj": "wq", "kv_a_proj_with_mqa": "wkv_a",
       "kv_a_layernorm": "kv_norm", "kv_b_proj": "wkv_b", "o_proj": "wo"}


def layer_types(c) -> tuple:
    """One of "kda" / "mla" per layer held, layers numbered from 1."""
    return tuple("mla" if l in c.full_attn_layers else "kda"
                 for l in range(1, c.num_hidden_layers + 1))


def sizes(c, router_trains: bool | None = None) -> ref.Sizes:
    """`c`: configs.KimiLinearConfig as run; `router_trains`: the net's
    own (`KimiLinearQNet.router_trains`), by default what it is without
    an exchange between the shares."""
    held = c.num_experts // c.shard_count
    return ref.Sizes(
        layer_types=layer_types(c), kda_heads=c.linear_num_heads,
        kda_head_dim=c.linear_head_dim, heads=c.num_attention_heads,
        kv_lora_rank=c.kv_lora_rank, qk_nope_head_dim=c.qk_nope_head_dim,
        qk_rope_head_dim=c.qk_rope_head_dim, v_head_dim=c.v_head_dim,
        top_k=c.num_experts_per_token,
        routed_scaling_factor=c.routed_scaling_factor,
        norm_topk_prob=c.moe_renormalize, rms_norm_eps=c.rms_norm_eps,
        first_expert=c.shard_index * held, experts_held=held,
        router_trains=(c.shard_count == 1 if router_trains is None
                       else router_trains),
        forced_balance=c.force_balanced_routing)


def _mixer(p: dict) -> dict:
    return KDA if "A_log" in p else MLA


def _layer(p: dict) -> dict:
    """One layer of the system's, under the reference's names."""
    out = {new: p[old] for old, new in {**NORMS, **_mixer(p)}.items()}
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

    names = {**NORMS, **(KDA if "a_log" in p else MLA)}
    out = {old: p[new] for old, new in names.items()}
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
