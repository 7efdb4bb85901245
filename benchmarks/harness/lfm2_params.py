"""The system's pytree (models/lfm2_moe_q.Lfm2MoeQNet) onto the plain
dict of benchmarks/reference/lfm2_moe_q.py, and the reference's `Sizes`
from the program's configuration - glm_params.py's counterpart for the
decoder family's sixth net, with the same functions under the same
names (the checks walk the layers through them). Matrix layouts agree
([in, out]; the system stacks the held experts on a leading axis, the
reference takes them as a list; the embedding [A, hidden] is the head
in both), so this is renaming and slicing only."""

from __future__ import annotations

from benchmarks.reference import lfm2_moe_q as ref

FFN = ("gate_proj", "up_proj", "down_proj")
# the system's name -> the reference's, by kind of operator
NORMS = {"operator_norm": "op_norm", "ffn_norm": "ffn_norm"}
CONV = {"in_proj": "w_in", "conv_weight": "conv_w", "out_proj": "w_out"}
FULL = {"q_proj": "wq", "k_proj": "wk", "v_proj": "wv", "out_proj": "wo",
        "q_layernorm": "q_norm", "k_layernorm": "k_norm"}


def sizes(c, router_trains: bool | None = None) -> ref.Sizes:
    """`c`: configs.Lfm2MoeConfig as run; `router_trains`: the net's
    own (`Lfm2MoeQNet.router_trains`), by default what it is without an
    exchange between the shares."""
    held = c.num_experts // c.shard_count
    return ref.Sizes(
        layer_types=tuple(c.layer_types), heads=c.num_attention_heads,
        kv_heads=c.num_key_value_heads,
        head_dim=c.head_dim or c.hidden_size // c.num_attention_heads,
        top_k=c.num_experts_per_tok,
        routed_scaling_factor=c.routed_scaling_factor,
        norm_topk_prob=c.norm_topk_prob, rms_norm_eps=c.norm_eps,
        rope_theta=c.rope_theta, first_expert=c.shard_index * held,
        experts_held=held,
        router_trains=(c.shard_count == 1 if router_trains is None
                       else router_trains),
        forced_balance=c.force_balanced_routing)


def _layer(p: dict) -> dict:
    """One layer of the system's, under the reference's names."""
    operator = CONV if "conv_weight" in p else FULL
    out = {new: p[old] for old, new in {**NORMS, **operator}.items()}
    mlp = p["mlp"]
    if "experts" not in mlp:
        out["dense"] = tuple(mlp[k] for k in FFN)
        return out
    held = mlp["experts"]["gate_proj"].shape[0]
    out["router"] = mlp["gate"]
    out["router_bias"] = mlp["e_score_correction_bias"]
    out["experts"] = [tuple(mlp["experts"][k][j] for k in FFN)
                      for j in range(held)]
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
            "final_norm": sys_params["embedding_norm"]}


def untied_view(sys_params: dict) -> dict:
    """The system's pytree under the names the family's forward walks
    read the ends by (decoder_sequence_checks.reference_net: `norm`,
    `lm_head`): the tied matrix under both of its uses' names, the same
    array."""
    return {**sys_params, "norm": sys_params["embedding_norm"],
            "lm_head": sys_params["embed_tokens"]}


def system_layer_gradients(p: dict) -> dict:
    """One layer of the reference's gradients renamed back into the
    system's names (the held experts stacked on a leading axis)."""
    import jax.numpy as jnp

    names = {**NORMS, **(CONV if "conv_w" in p else FULL)}
    out = {old: p[new] for old, new in names.items()}
    if "dense" in p:
        out["mlp"] = dict(zip(FFN, p["dense"]))
    else:
        out["mlp"] = {
            "gate": p["router"],
            "e_score_correction_bias": p["router_bias"],
            "experts": {k: jnp.stack([e[i] for e in p["experts"]])
                        for i, k in enumerate(FFN)}}
    return out


def system_gradients(ref_grads: dict) -> dict:
    """The reference's gradients renamed back into the system's pytree,
    so the two trees compare leaf by leaf."""
    return {"embed_tokens": ref_grads["embed"],
            "layers": [system_layer_gradients(p)
                       for p in ref_grads["layers"]],
            "embedding_norm": ref_grads["final_norm"]}
