"""The system's pytree (models/smallthinker_q.SmallThinkerQNet) onto the
plain dict of benchmarks/reference/smallthinker_q.py, and the
reference's `Sizes` from the program's configuration - afmoe_params.py's
counterpart for the decoder family's third net, with the same functions
under the same names (the checks walk the layers through them). Matrix
layouts agree ([in, out]; the system stacks the held experts on a
leading axis, the reference takes them as a list), so this is renaming
and slicing only."""

from __future__ import annotations

from benchmarks.reference import smallthinker_q as ref

FFN = ("gate_proj", "up_proj", "down_proj")
# the system's name -> the reference's, one layer's attention and norms
NAMES = {"input_layernorm": "attn_norm", "q_proj": "wq", "k_proj": "wk",
         "v_proj": "wv", "o_proj": "wo",
         "post_attention_layernorm": "ffn_norm"}
KINDS = {(1, 1): "sliding_attention", (0, 0): "full_attention"}


def sizes(st, router_trains: bool | None = None) -> ref.Sizes:
    """`st`: configs.SmallThinkerConfig as run; `router_trains`: the
    net's own (`SmallThinkerQNet.router_trains`), by default what it is
    without an exchange between the shares."""
    pairs = tuple(zip(st.rope_layout, st.sliding_window_layout))
    if not set(pairs) <= set(KINDS):
        raise ValueError(
            "the reference has the model's two kinds of layer: RoPE with "
            "the window, or neither (rope_layout == sliding_window_layout)")
    held = st.moe_num_primary_experts // st.shard_count
    return ref.Sizes(
        heads=st.num_attention_heads, kv_heads=st.num_key_value_heads,
        head_dim=st.head_dim, layer_types=tuple(KINDS[p] for p in pairs),
        window=st.sliding_window_size,
        top_k=st.moe_num_active_primary_experts,
        rms_norm_eps=st.rms_norm_eps, rope_theta=st.rope_theta,
        first_expert=st.shard_index * held, experts_held=held,
        router_trains=(st.shard_count == 1 if router_trains is None
                       else router_trains),
        forced_balance=st.force_balanced_routing)


def _layer(p: dict) -> dict:
    """One layer of the system's, under the reference's names."""
    out = {new: p[old] for old, new in NAMES.items()}
    mlp = p["mlp"]
    out["router"] = mlp["gate"]
    out["experts"] = [tuple(mlp["experts"][k][j] for k in FFN)
                      for j in range(mlp["experts"]["gate_proj"].shape[0])]
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
    out["mlp"] = {
        "gate": p["router"],
        "experts": {k: jnp.stack([e[i] for e in p["experts"]])
                    for i, k in enumerate(FFN)}}
    return out


def system_gradients(ref_grads: dict) -> dict:
    """The reference's gradients renamed back into the system's pytree,
    so the two trees compare leaf by leaf."""
    return {"embed_tokens": ref_grads["embed"],
            "layers": [system_layer_gradients(p)
                       for p in ref_grads["layers"]],
            "norm": ref_grads["final_norm"], "lm_head": ref_grads["head"]}
