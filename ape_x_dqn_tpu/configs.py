"""Run configuration system.

The reference ships five named configurations (SURVEY.md §2.1, attested by
BASELINE.json `configs`). Each is a preset here; every field can be
overridden from the CLI (``runtime/train.py``) or programmatically via
``dataclasses.replace``.

Hyperparameter defaults follow the published papers the reference
implements (Horgan et al. 2018 Ape-X; Kapturowski et al. 2019 R2D2;
Schaul et al. 2016 PER) as recorded in BASELINE.md.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any


@dataclass(frozen=True)
class EnvConfig:
    id: str = "CartPole-v1"
    kind: str = "cartpole"  # cartpole | cartpole_po | atari | control | synthetic_atari | synthetic_tokens
    # Atari preprocessing (SURVEY.md §2.2 "Env wrappers")
    frame_skip: int = 4
    frame_stack: int = 4
    resize: int = 84
    max_noop_start: int = 30
    episodic_life: bool = True
    clip_rewards: bool = True
    max_episode_frames: int = 108_000  # 30 min @ 60Hz, standard ALE cap
    # Force ALE's 18-action legal set instead of the per-game minimal
    # set. Auto-enabled for id="atari57" fleets (one shared Q-net across
    # games with heterogeneous minimal sets), and set by per-game eval
    # workers evaluating such a net so action indices stay aligned.
    full_action_set: bool = False
    # synthetic_tokens: how many token ids the environment emits and
    # accepts as actions — the vocabulary rows the Q-network holds
    # (the decoder block's vocab_size / shard_count)
    num_tokens: int = 256


def _check_share(block: str, cfg: Any, experts: str) -> None:
    """A routed block's share of its deployment: `shard_index` names one
    of `shard_count` chips, which divide the routed experts (the
    block's field `experts`) evenly, and the vocabulary's rows go as
    many ways, or `vocab_shard_count` ways where the block has that
    field and it is not 0."""
    if not 0 <= cfg.shard_index < cfg.shard_count:
        raise ValueError(
            f"network.{block}.shard_index must be in [0, "
            f"{cfg.shard_count}) (got {cfg.shard_index})")
    vocab_shards = getattr(cfg, "vocab_shard_count", 0) or cfg.shard_count
    if (getattr(cfg, experts) % cfg.shard_count
            or cfg.vocab_size % vocab_shards):
        raise ValueError(
            f"network.{block}.shard_count={cfg.shard_count} must divide "
            f"{experts}={getattr(cfg, experts)}, and the vocabulary's "
            f"{vocab_shards} shares vocab_size={cfg.vocab_size}")


@dataclass(frozen=True)
class GlmMoeConfig:
    """The decoder of network.kind="glm_moe_q" (models/glm_moe_q.py),
    under the key names of the model's own config.json (model_type
    glm4_moe_lite); defaults are GLM-4.7-Flash's. Multi-head latent
    attention, `first_k_dense_replace` leading dense SwiGLU layers,
    then layers of `n_routed_experts` routed experts (sigmoid scores,
    top-`num_experts_per_tok` of score + a fixed selection bias,
    weights normalised and scaled) beside `n_shared_experts` shared
    ones; untied embedding and head."""

    hidden_size: int = 2048
    intermediate_size: int = 10240      # the dense layers' SwiGLU
    moe_intermediate_size: int = 1536   # each routed / shared expert
    num_hidden_layers: int = 47
    first_k_dense_replace: int = 1
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    n_group: int = 1                    # only 1 is built: no group stage
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.8
    vocab_size: int = 154_880
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1_000_000.0
    # This chip's share of a deployment in which `shard_count` chips
    # share each layer: attention, router and shared expert are
    # replicated, the routed experts [shard_index * n / shard_count,
    # (shard_index + 1) * n / shard_count) and as many of the
    # vocabulary's rows live here. The router still scores all
    # n_routed_experts; what absent experts would add is left out
    # (models/glm_moe_q.py). 1 = the whole layer.
    shard_count: int = 1
    shard_index: int = 0
    # For measuring with random weights only (Megatron-LM's
    # --moe-router-force-load-balancing is the precedent): the top-k
    # SELECTION follows a fixed pseudo-random function of (token id,
    # position, layer, expert) instead of score + bias, so every expert
    # sees its even share of the rows whatever the weights are; the
    # weights of the selected experts are still the router's. Random
    # weights select nearly the same k experts for every token
    # (models/glm_moe_q.py says why), which no trained checkpoint does.
    force_balanced_routing: bool = False

    def __post_init__(self) -> None:
        _check_share("glm", self, "n_routed_experts")


_PUBLISHED_AFMOE_LAYER_TYPES = (
    "sliding_attention", "sliding_attention", "sliding_attention",
    "full_attention") * 8


@dataclass(frozen=True)
class AfmoeConfig:
    """The decoder of network.kind="afmoe_q" (models/afmoe_q.py), under
    the key names of the model's own config.json (model_type afmoe);
    defaults are Trinity-Mini's. Grouped-query attention with a gated
    output, `layer_types[i]` "sliding_attention" (RoPE, the last
    `sliding_window` keys) or "full_attention" (no position encoding,
    every key); `num_dense_layers` leading dense SwiGLU layers, then
    layers of `num_experts` routed experts (sigmoid scores, top-
    `num_experts_per_tok` of score + a fixed selection bias, weights
    normalised if `route_norm` and times `route_scale`) beside
    `num_shared_experts` shared ones; embedding scaled by
    sqrt(hidden_size) if `mup_enabled`; untied embedding and head."""

    hidden_size: int = 2048
    intermediate_size: int = 6144       # the dense layers' SwiGLU
    moe_intermediate_size: int = 1024   # each routed / shared expert
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    # one entry per layer held, in order; a run that holds fewer layers
    # than the model names the kinds of the ones it holds
    layer_types: tuple[str, ...] = _PUBLISHED_AFMOE_LAYER_TYPES
    sliding_window: int = 2048
    num_experts: int = 128
    num_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 1                    # only 1 is built: no group stage
    topk_group: int = 1
    route_norm: bool = True
    route_scale: float = 2.826
    mup_enabled: bool = True
    vocab_size: int = 200_192
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10_000.0
    # the three below are GlmMoeConfig's, with the same meaning: this
    # chip's share of a deployment in which `shard_count` chips share
    # each layer (routed experts [shard_index * n / shard_count, ...)
    # and as many vocabulary rows live here), and the selection forced
    # balanced for measuring with random weights
    shard_count: int = 1
    shard_index: int = 0
    force_balanced_routing: bool = False
    # over how many chips the embedding's and the head's rows are
    # divided, where that is fewer than share the experts (128 experts
    # go sixteen ways at 8 a chip; a sixteenth of the vocabulary would
    # be under an eighth, the least that is still the model's head);
    # rows [i * V / n, (i + 1) * V / n), i = shard_index % n, live here.
    # 0: as shard_count
    vocab_shard_count: int = 0

    def __post_init__(self) -> None:
        _check_share("afmoe", self, "num_experts")
        # (that there is one entry per layer held is the net's to
        # check: overrides set the two fields one after the other)
        kinds = {"sliding_attention", "full_attention"}
        if not set(self.layer_types) <= kinds:
            raise ValueError(
                f"network.afmoe.layer_types must name {sorted(kinds)} "
                f"(got {self.layer_types})")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"network.afmoe.num_key_value_heads="
                f"{self.num_key_value_heads} must divide "
                f"num_attention_heads={self.num_attention_heads}")


_PUBLISHED_SMALLTHINKER_LAYOUT = (0, 1, 1, 1) * 13


@dataclass(frozen=True)
class SmallThinkerConfig:
    """The decoder of network.kind="smallthinker_q"
    (models/smallthinker_q.py), under the key names of the model's own
    config.json (PowerInfer/SmallThinker-21BA3B-Instruct); defaults are
    that model's. Grouped-query attention, layer i with a window of
    `sliding_window_size` keys if `sliding_window_layout[i]` and RoPE
    if `rope_layout[i]` (else every earlier key, no position encoding);
    every layer `moe_num_primary_experts` routed ReGLU experts of
    `moe_ffn_hidden_size`, top-`moe_num_active_primary_experts` of a
    router that reads the attention's input, weights the softmax over
    the selected logits; no shared expert, no dense layer; untied
    embedding and head."""

    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_ffn_hidden_size: int = 768
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    # only True/True is built: together they are the softmax over the
    # selected logits
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    # one entry per layer held, in order (1: RoPE / a sliding window);
    # a run that holds fewer layers than the model names the ones it
    # holds
    rope_layout: tuple[int, ...] = _PUBLISHED_SMALLTHINKER_LAYOUT
    sliding_window_layout: tuple[int, ...] = _PUBLISHED_SMALLTHINKER_LAYOUT
    sliding_window_size: int = 4096
    max_position_embeddings: int = 16_384   # the most tokens in one pass
    rope_theta: float = 1_500_000.0
    vocab_size: int = 151_936
    rms_norm_eps: float = 1e-6
    # the three below are GlmMoeConfig's, with the same meaning: this
    # chip's share of a deployment in which `shard_count` chips share
    # each layer (routed experts [shard_index * n / shard_count, ...)
    # and as many vocabulary rows live here), and the selection forced
    # balanced for measuring with random weights
    shard_count: int = 1
    shard_index: int = 0
    force_balanced_routing: bool = False

    def __post_init__(self) -> None:
        _check_share("smallthinker", self, "moe_num_primary_experts")
        # (that there is one entry per layer held is the net's to
        # check: overrides set the fields one after the other)
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"network.smallthinker.num_key_value_heads="
                f"{self.num_key_value_heads} must divide "
                f"num_attention_heads={self.num_attention_heads}")


@dataclass(frozen=True)
class OuroConfig:
    """The decoder of network.kind="ouro_q" (models/ouro_q.py), under
    the key names of the model's own config.json (ByteDance/Ouro-2.6B,
    `model_type` ouro); defaults are that model's. A stack of
    `num_hidden_layers` blocks of full causal attention (no grouping:
    as many key-value heads as query heads) and a dense SwiGLU MLP of
    `intermediate_size`, RUN `total_ut_steps` TIMES WITH THE SAME
    WEIGHTS; untied embedding and head. The net has no expert layer and
    so no share of one: a chip's cut is a number of layers."""

    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    intermediate_size: int = 5632
    # the whole stack is applied this many times a forward pass
    total_ut_steps: int = 4
    # the exit gate's cumulative mass at which a token would stop
    # looping; only 1.0 (the published value: nothing stops, every step
    # runs) is built
    early_exit_threshold: float = 1.0
    max_position_embeddings: int = 65_536   # the most tokens in one pass
    rope_theta: float = 1_000_000.0
    vocab_size: int = 49_152
    rms_norm_eps: float = 1e-6

    def __post_init__(self) -> None:
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"network.ouro.num_key_value_heads="
                f"{self.num_key_value_heads} must divide "
                f"num_attention_heads={self.num_attention_heads}")
        if self.total_ut_steps < 1:
            raise ValueError(
                f"network.ouro.total_ut_steps={self.total_ut_steps}: the "
                f"stack runs at least once")


_PUBLISHED_KIMI_FULL_ATTN_LAYERS = (4, 8, 12, 16, 20, 24, 27)


@dataclass(frozen=True)
class KimiLinearConfig:
    """The decoder of network.kind="kimi_linear_q"
    (models/kimi_linear_q.py), under the key names of the model's own
    config.json (moonshotai/Kimi-Linear-48B-A3B-Instruct, `model_type`
    kimi_linear; its nested `linear_attn_config` flat here as
    `linear_*` / `full_attn_layers`); defaults are that model's. Layers
    are numbered from 1 as in that file: layer l is multi-head latent
    attention WITHOUT any rotation (`mla_use_nope`) if l is in
    `full_attn_layers`, else Kimi Delta Attention (a gated delta rule
    with a decay per key channel behind a short causal convolution);
    the first `first_k_dense_replace` layers' FFN is one dense SwiGLU,
    every other layer's `num_experts` routed experts (sigmoid scores,
    top-`num_experts_per_token` of score + a fixed selection bias,
    weights normalised if `moe_renormalize` and times
    `routed_scaling_factor`) beside `num_shared_experts` shared ones;
    untied embedding and head. The query has no low rank (`q_lora_rank`
    null in the model's file: no field)."""

    hidden_size: int = 2304
    intermediate_size: int = 9216       # the dense layers' SwiGLU
    moe_intermediate_size: int = 1024   # each routed / shared expert
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    # the MLA layers, numbered from 1; every other layer is KDA. A run
    # that holds fewer layers holds layers 1 .. num_hidden_layers
    full_attn_layers: tuple[int, ...] = _PUBLISHED_KIMI_FULL_ATTN_LAYERS
    # KDA (linear_attn_config): heads, the key and value size of each,
    # the causal convolution's kernel
    linear_num_heads: int = 32
    linear_head_dim: int = 128
    linear_short_conv_kernel_size: int = 4
    # MLA
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64      # plain dims: nothing is rotated
    v_head_dim: int = 128
    mla_use_nope: bool = True       # only True is built
    num_experts: int = 256
    num_shared_experts: int = 1
    num_experts_per_token: int = 8
    num_expert_group: int = 1           # only 1 is built: no group stage
    topk_group: int = 1
    moe_renormalize: bool = True
    routed_scaling_factor: float = 2.446
    vocab_size: int = 163_840
    rms_norm_eps: float = 1e-5
    # the four below are AfmoeConfig's, with the same meaning: this
    # chip's share of a deployment in which `shard_count` chips share
    # each layer's experts and `vocab_shard_count` (0: as shard_count)
    # the embedding's and the head's rows, and the selection forced
    # balanced for measuring with random weights
    shard_count: int = 1
    shard_index: int = 0
    vocab_shard_count: int = 0
    force_balanced_routing: bool = False

    def __post_init__(self) -> None:
        _check_share("kimi_linear", self, "num_experts")


_PUBLISHED_LFM2_LAYER_TYPES = ("conv", "conv") + (
    "full_attention", "conv", "conv", "conv") * 9 + ("full_attention", "conv")


@dataclass(frozen=True)
class Lfm2MoeConfig:
    """The decoder of network.kind="lfm2_moe_q" (models/lfm2_moe_q.py),
    under the key names of the model's own config.json
    (LiquidAI/LFM2-24B-A2B, `model_type` lfm2_moe; its nested
    `rope_parameters.rope_theta` flat here as `rope_theta`); defaults
    are that model's. `layer_types[i]` "conv" is the gated short
    convolution (two multiplicative gates around a causal depthwise
    filter of `conv_L_cache` taps, no activation), "full_attention"
    grouped-query attention with q/k head norms and RoPE on every dim;
    the first `num_dense_layers` layers' FFN is one dense SwiGLU, every
    other layer's `num_experts` routed experts (sigmoid scores, top-
    `num_experts_per_tok` of score + a fixed selection bias if
    `use_expert_bias`, weights normalised if `norm_topk_prob` and times
    `routed_scaling_factor`), none shared. The head is the embedding
    (`tie_embedding`; only True is built)."""

    hidden_size: int = 2048
    intermediate_size: int = 11776      # the dense layers' SwiGLU
    moe_intermediate_size: int = 1536   # each routed expert
    num_hidden_layers: int = 40
    num_dense_layers: int = 2
    # one kind per layer HELD (a run that holds fewer layers names
    # theirs): "conv" | "full_attention"
    layer_types: tuple[str, ...] = _PUBLISHED_LFM2_LAYER_TYPES
    conv_L_cache: int = 3       # the filter's taps; a prefix leaves one less
    conv_bias: bool = False     # only False is built
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    # the model's file has none (null): 0 is hidden_size over the heads
    head_dim: int = 0
    rope_theta: float = 1_000_000.0
    max_position_embeddings: int = 128_000
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True        # only True is built
    vocab_size: int = 65_536
    norm_eps: float = 1e-5
    tie_embedding: bool = True          # only True is built
    # the four below are AfmoeConfig's, with the same meaning: this
    # chip's share of a deployment in which `shard_count` chips share
    # each layer's experts and `vocab_shard_count` (0: as shard_count)
    # the embedding's rows, and the selection forced balanced for
    # measuring with random weights
    shard_count: int = 1
    shard_index: int = 0
    vocab_shard_count: int = 0
    force_balanced_routing: bool = False

    def __post_init__(self) -> None:
        _check_share("lfm2_moe", self, "num_experts")


_PUBLISHED_SALA_MIXERS = (
    ("minicpm4",) + ("lightning-attn",) * 8 + ("minicpm4",)
    + ("lightning-attn",) * 6 + ("minicpm4",) * 2 + ("lightning-attn",) * 4
    + ("minicpm4",) + ("lightning-attn",) * 6 + ("minicpm4",) * 3)


@dataclass(frozen=True)
class MiniCpmSalaConfig:
    """The decoder of network.kind="minicpm_sala_q"
    (models/minicpm_sala_q.py), under the key names of the model's own
    config.json (openbmb/MiniCPM-SALA, `model_type` minicpm_sala);
    defaults are that model's. `mixer_types[i]` "minicpm4" is grouped-
    query attention WITHOUT position encoding (`attn_use_rope` false)
    over key blocks the data chooses once a context passes
    `sparse_dense_len` (InfLLM v2; ops/block_select_attention.py),
    "lightning-attn" linear attention with one scalar decay a head and
    a float32 `lightning_head_dim` x `lightning_head_dim` state
    (ops/lightning_attention.py); both behind q/k head norms
    (`qk_norm`) and a sigmoid output gate, every FFN one dense SwiGLU,
    untied embedding and head. The row has no `sparse_config`: the
    seven `sparse_*` sizes are the MiniCPM4 report's as recalled
    (`assumed` in the benchmark's configuration file). The net has no
    expert layer and so no share of one: a chip's cut is a number of
    layers, set with `num_hidden_layers` AND `mixer_types` (the kinds of
    the layers held; the net checks that they agree when it is built,
    so that two overrides may arrive one after the other)."""

    hidden_size: int = 4096
    num_hidden_layers: int = 32
    mixer_types: tuple[str, ...] = _PUBLISHED_SALA_MIXERS
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    lightning_use_rope: bool = True
    attn_use_rope: bool = False
    qk_norm: bool = True
    use_output_gate: bool = True
    use_output_norm: bool = True
    attn_use_output_gate: bool = True
    intermediate_size: int = 16_384
    rope_theta: float = 10_000.0
    # muP: x0 = scale_emb E[token]; a sublayer's output times
    # scale_depth / sqrt(depth_scale_layers); Q over hidden_size /
    # dim_model_base. The depth is the PUBLISHED one whatever a cut
    # holds, so a pipeline stage's blocks are the model's
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    depth_scale_layers: int = 32
    dim_model_base: int = 256
    max_position_embeddings: int = 524_288
    vocab_size: int = 73_448
    rms_norm_eps: float = 1e-6
    # the selection (ops/block_select_attention.Sizes)
    sparse_block_size: int = 64
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_init_blocks: int = 1
    sparse_window_size: int = 2_048
    sparse_topk: int = 64
    sparse_dense_len: int = 8_192


@dataclass(frozen=True)
class JambaConfig:
    """The decoder of network.kind="jamba_q" (models/jamba_q.py), under
    the key names of the model's own config.json
    (ai21labs/AI21-Jamba2-3B, `model_type` jamba); defaults are that
    model's. Layer i is grouped-query attention WITHOUT position
    encoding iff `i % attn_layer_period == attn_layer_offset`, else a
    Mamba-1 mixer: `mamba_expand` x hidden channels behind a causal
    depthwise conv of `mamba_d_conv` taps (with a bias,
    `mamba_conv_bias`) and a SiLU, a selective scan with a float32
    state of `mamba_d_state` coordinates a channel
    (ops/selective_scan.py) whose step, B and C come from a projection
    of rank `mamba_dt_rank` + 2 `mamba_d_state` through Jamba's three
    inner RMSNorms, a SiLU gate. Every FFN is one dense SwiGLU
    (`num_experts` 1: the `expert_layer_*` keys choose among no
    experts), embedding and head tied. The net has no expert layer and
    so no share of one; a served chip holds the whole model."""

    hidden_size: int = 2560
    num_hidden_layers: int = 28
    attn_layer_offset: int = 7
    attn_layer_period: int = 14
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    intermediate_size: int = 8192
    mamba_d_conv: int = 4
    mamba_d_state: int = 16
    mamba_dt_rank: int = 160
    mamba_expand: int = 2
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    num_experts: int = 1
    max_position_embeddings: int = 262_144
    vocab_size: int = 65_536
    rms_norm_eps: float = 1e-6


@dataclass(frozen=True)
class NetworkConfig:
    # mlp | nature_cnn | lstm_q | dpg | a decoder's (models.DECODERS)
    kind: str = "mlp"
    mlp_hidden: tuple[int, ...] = (256, 256)
    cnn_channels: tuple[int, ...] = (32, 64, 64)
    cnn_kernels: tuple[int, ...] = (8, 4, 3)
    cnn_strides: tuple[int, ...] = (4, 2, 1)
    torso_dense: int = 512
    dueling: bool = True
    lstm_size: int = 512
    # DPG (continuous control)
    dpg_hidden: tuple[int, ...] = (300, 200)
    # Compute dtype for the forward/backward pass (params stay f32).
    compute_dtype: str = "bfloat16"
    # the decoder of kind="glm_moe_q" (token-level Q-learning)
    glm: GlmMoeConfig = field(default_factory=GlmMoeConfig)
    # the decoder of kind="afmoe_q" (the same family, another model)
    afmoe: AfmoeConfig = field(default_factory=AfmoeConfig)
    # the decoder of kind="smallthinker_q" (the same family, a third)
    smallthinker: SmallThinkerConfig = field(
        default_factory=SmallThinkerConfig)
    # the decoder of kind="ouro_q" (the same family; no expert layer)
    ouro: OuroConfig = field(default_factory=OuroConfig)
    # the decoder of kind="kimi_linear_q" (the same family: KDA scan
    # layers beside latent attention, with experts)
    kimi_linear: KimiLinearConfig = field(default_factory=KimiLinearConfig)
    # the decoder of kind="lfm2_moe_q" (the same family: gated short
    # convolutions beside full attention, with experts, a tied head)
    lfm2_moe: Lfm2MoeConfig = field(default_factory=Lfm2MoeConfig)
    # the decoder of kind="minicpm_sala_q" (the same family: attention
    # over key blocks the data chooses beside lightning linear
    # attention, dense FFNs; the first net the server keeps in slots)
    minicpm_sala: MiniCpmSalaConfig = field(
        default_factory=MiniCpmSalaConfig)
    # the decoder of kind="jamba_q" (the same family: Mamba-1 mixers
    # beside full attention, dense FFNs, a tied head; the second net
    # the server keeps in slots)
    jamba: JambaConfig = field(default_factory=JambaConfig)


@dataclass(frozen=True)
class ReplayConfig:
    kind: str = "prioritized"  # uniform | prioritized | sequence
    capacity: int = 2_000_000
    alpha: float = 0.6
    beta: float = 0.4
    eps: float = 1e-6  # priority floor
    # Storage layout for pixel configs: "flat" stores stacked obs pairs
    # per transition; "frame_ring" stores single frames once and rebuilds
    # stacks with a device gather at sample time — ~6-7x less HBM and
    # ingest bandwidth (replay/frame_ring.py; SURVEY.md §7 hard part 2)
    storage: str = "flat"  # flat | frame_ring
    seg_transitions: int = 16  # transitions per shipped frame segment
    # segments per ingest add dispatch: bigger blocks = fewer add
    # dispatches contending with train_many for the device queue and
    # host->device link (the round-3 live soak measured 70 -> 125
    # grad-steps/s going 4 -> 16 under concurrent ingest; PERF.md
    # "Live soak"). Latency cost: a block buffers
    # dp * segs_per_add * seg_transitions transitions host-side.
    segs_per_add: int = 16
    # R2D2 sequence replay (SURVEY.md §3.4)
    seq_length: int = 80
    seq_overlap: int = 40
    burn_in: int = 40
    # priority = eta*max|td| + (1-eta)*mean|td| over the sequence
    priority_eta: float = 0.9
    min_fill: int = 50_000  # transitions before learning starts
    # -- zero-copy pipelined ingest staging (runtime/ingest.py) --
    # Wire batches decode DIRECTLY into preallocated fixed-shape staging
    # blocks at a write cursor (one copy per wire byte, contiguous by
    # construction — PERF.md round 5: contiguity is ~80 vs ~3,000
    # items/s of device_put), double-buffered so block N+1 decodes while
    # block N's async device_put is in flight.
    # ingest_coalesce: staged blocks fused into ONE donated add_many
    # dispatch — _state_lock is taken once per group instead of once per
    # block, so ingest adds stop interleaving with train_many. Latency
    # cost: a group buffers coalesce * block units host-side before
    # shipping (idle drains flush partial groups block-by-block).
    ingest_coalesce: int = 4
    # host staging buffers to rotate through (>= 2 for double buffering)
    stage_buffers: int = 2
    # -- tiered cold store (replay/cold_store.py), default OFF ----------
    # cold_tier_capacity > 0 enables the host-RAM cold tier behind the
    # device ring: when the ring is full, each ingest block overwrites
    # the ring's LOWEST-priority-mass region (instead of blind FIFO) and
    # the displaced region is delta+deflate-compressed into fixed-size
    # host segments carrying per-segment priority summaries. Capacity is
    # in TRANSITIONS; sizing rule of thumb: the cold tier holds ~10x
    # less bytes/transition than the ring (PERF.md "Tiered replay"), so
    # 8-64x the ring capacity costs host RAM comparable to the ring's
    # HBM. 0 keeps the default single-tier path bitwise untouched.
    cold_tier_capacity: int = 0
    # compressed cold segments decompressed + restaged (through the
    # SAME IngestStager -> add_many path as fresh actor data) per idle
    # refill tick, highest priority mass first; 0 disables recall while
    # still capturing evictions
    cold_tier_refill: int = 1
    # zlib level for cold segments (1 = speed, the wire codec's choice)
    cold_tier_compress_level: int = 1
    # -- disk-spill rung below the cold store (replay/disk_store.py),
    # default OFF ------------------------------------------------------
    # cold_tier_disk_capacity > 0 (transitions; requires the RAM tier)
    # adds an append-only segment-file rung under the cold store: RAM
    # door losers (displaced victims + live door-dropped candidates)
    # spill to disk via an async writeback thread instead of vanishing,
    # and the idle refill tick promotes the heaviest disk segments back
    # through the RAM door. Retention becomes a disk-provisioning knob
    # (10^8+ transitions at the cold tier's ~10x compression). 0 keeps
    # the RAM-only tier bitwise untouched.
    cold_tier_disk_capacity: int = 0
    # segment-file directory; REQUIRED non-empty when the disk rung is
    # on (an existing directory is recovered: index rebuilt from record
    # headers, torn tails truncated)
    cold_tier_disk_dir: str = ""
    # bounded writeback queue depth (segments). The ship path NEVER
    # waits on disk: a full queue counts cold_disk_queue_full and drops.
    cold_tier_disk_queue: int = 16
    # roll segment files at this size; compaction granularity
    cold_tier_disk_file_bytes: int = 64 * 1024 * 1024
    # compact a sealed file when its dead-byte fraction exceeds this
    cold_tier_disk_compact_frac: float = 0.5
    # disk segments promoted back toward the RAM store per idle refill
    # tick (after RAM recalls); 0 disables promotion while still
    # capturing spills
    cold_tier_disk_promote: int = 1

    def __post_init__(self) -> None:
        if self.cold_tier_capacity < 0:
            raise ValueError(
                f"replay.cold_tier_capacity must be >= 0 "
                f"(got {self.cold_tier_capacity}); 0 disables the tier")
        if self.cold_tier_capacity > 0:
            # guided error at CONFIG time, not mid-eviction: the cold
            # tier cannot run without the delta+deflate building blocks
            # (a host without g++ is fine — comm/native.py runs the
            # bit-identical numpy codec there, and ColdStore logs a
            # one-liner saying so)
            from ape_x_dqn_tpu.replay.cold_store import codec_status
            ok, detail = codec_status()
            if not ok:
                raise ValueError(
                    f"replay.cold_tier_capacity={self.cold_tier_capacity} "
                    f"needs the delta+deflate codec, which failed to "
                    f"import: {detail}. Fix the install (ape_x_dqn_tpu."
                    f"comm.native must be importable — no compiler or "
                    f".so is required, the numpy fallback is "
                    f"bit-identical) or set replay.cold_tier_capacity=0 "
                    f"to run single-tier.")
        if self.cold_tier_disk_capacity < 0:
            raise ValueError(
                f"replay.cold_tier_disk_capacity must be >= 0 (got "
                f"{self.cold_tier_disk_capacity}); 0 disables the disk "
                f"rung")
        if self.cold_tier_disk_capacity > 0:
            if self.cold_tier_capacity <= 0:
                raise ValueError(
                    "replay.cold_tier_disk_capacity > 0 requires the "
                    "RAM cold tier (replay.cold_tier_capacity > 0): "
                    "the disk rung only sees segments through the RAM "
                    "store's admission door")
            if not self.cold_tier_disk_dir:
                raise ValueError(
                    "replay.cold_tier_disk_capacity > 0 requires "
                    "replay.cold_tier_disk_dir (the segment-file "
                    "directory; created if missing, recovered if it "
                    "holds prior segment files)")
            if self.cold_tier_disk_queue < 1:
                raise ValueError(
                    f"replay.cold_tier_disk_queue must be >= 1 (got "
                    f"{self.cold_tier_disk_queue}): the writeback "
                    f"queue needs at least one slot")
            if self.cold_tier_disk_file_bytes < 1024:
                raise ValueError(
                    f"replay.cold_tier_disk_file_bytes must be >= 1024 "
                    f"(got {self.cold_tier_disk_file_bytes}); one file "
                    f"must hold at least one record")
            if not (0.0 < self.cold_tier_disk_compact_frac <= 1.0):
                raise ValueError(
                    f"replay.cold_tier_disk_compact_frac must be in "
                    f"(0, 1] (got {self.cold_tier_disk_compact_frac})")
            if self.cold_tier_disk_promote < 0:
                raise ValueError(
                    f"replay.cold_tier_disk_promote must be >= 0 (got "
                    f"{self.cold_tier_disk_promote})")


@dataclass(frozen=True)
class LearnerConfig:
    batch_size: int = 512
    lr: float = 2.5e-4 / 4
    adam_eps: float = 1.5e-7
    gamma: float = 0.99
    n_step: int = 3
    target_sync_every: int = 2500
    max_grad_norm: float = 40.0
    huber_delta: float = 1.0
    double_dqn: bool = True
    value_rescale: bool = False  # R2D2 h(x) transform
    publish_every: int = 50  # learner→actor weight publish cadence (steps)
    # grad-steps fused into one train_many dispatch in the driver hot loop
    # (lax.scan on device; no host round-trips between steps)
    train_chunk: int = 8
    # K-batch sampling relaxation (SURVEY.md §3.3's sample<-update race,
    # resolved by relaxation): sample K*B items in ONE stratified tree
    # descent, run K grad-steps over the K chunks, write priorities back
    # ONCE. Within-chunk priority staleness (chunk j+1's sample does not
    # see chunk j's TD updates) matches the reference's async
    # replay-server semantics, where the host sampler always lags the
    # learner by an update round-trip. 1 = exact per-step semantics.
    # A/B'd on the real chip: PERF.md "K-batch sampling".
    sample_chunk: int = 1
    # Double-buffered replay sampling (PERF.md "Ideas not yet taken",
    # now "Prefetch A/B"): pipeline the learner cycle one dispatch deep
    # so the NEXT macro-step's tree descent + frame gather overlaps the
    # CURRENT macro-step's K grad-steps. The prefetched sample is drawn
    # against priorities that predate the in-flight write-back — a
    # one-dispatch staleness identical in kind to sample_chunk's
    # within-chunk staleness and to the reference's async host-side
    # replay server (its sampler always lags the learner by an update
    # round-trip). Default off until an on-chip on/off by the pairs
    # rule clears the noise band (ROADMAP D4-prefetch).
    sample_prefetch: bool = False
    # Pacing: cap grad-steps at this multiple of ingested transitions
    # (None = free-run, the Ape-X default where the learner trains as
    # fast as the device allows). Bounds replay overfit when actors are
    # slow relative to the learner, and on shared-core test hosts stops
    # the learner starving actor inference.
    steps_per_frame_cap: float | None = None
    # DPG
    critic_lr: float = 1e-3
    policy_lr: float = 1e-4
    tau: float = 0.005  # soft target update for DPG


@dataclass(frozen=True)
class ActorConfig:
    num_actors: int = 8
    # Envs per actor thread, K >= 1 (runtime/actor.py): one thread steps
    # K envs and makes ONE batched inference query per vector step, so
    # RPC round-trips amortize K ways and the server sees batch-K work
    # (SURVEY.md §2.4 "inference batching parallelism", §7 hard part 3).
    # The eps schedule spans num_actors * envs_per_actor global slots.
    envs_per_actor: int = 1
    # eps_i = base_eps ** (1 + alpha * i / (N-1))  (Horgan et al. 2018)
    base_eps: float = 0.4
    eps_alpha: float = 7.0
    ingest_batch: int = 50  # transitions buffered before shipping
    param_pull_every: int = 400  # env steps between parameter pulls
    # Elastic recovery (SURVEY.md §5): a crashed actor is rebuilt (fresh
    # env + n-step state) and resumes its remaining frame budget, up to
    # this many times per actor slot; Ape-X tolerates actor loss, so a
    # restart costs only the crashed actor's in-flight transitions
    max_restarts: int = 2
    # Fleet supervisor (runtime/driver._supervise_tick): when obs
    # heartbeats flag a LOCAL actor thread as stalled past the
    # watchdog timeout, restart its slot in place (fresh env + actor,
    # remaining frame budget) instead of raising StallError for the
    # whole run. Each slot gets supervisor_max_restarts supervised
    # restarts; past the budget the slot is QUARANTINED — heartbeat
    # cleared, actor_quarantines counter + attributed JSONL event —
    # and the run continues degraded, never a crash loop. Stalls of
    # the learner/ingest/inference-server still raise (a driver
    # cannot restart its own learner), and remote-peer stalls are
    # counted + quarantined, not fatal (the peer's own host owns its
    # recovery).
    supervise: bool = True
    supervisor_max_restarts: int = 3
    # multihost: how long an actor-less listening learner waits for its
    # first remote actor-host connection before it may report idle
    # (raise for cluster queues / slow container pulls; too low and a
    # learner-only fleet self-terminates with 0 grad steps)
    remote_boot_grace_s: float = 300.0
    # continuous-control exploration noise stddev (DPG)
    noise_sigma: float = 0.2


@dataclass(frozen=True)
class InferenceConfig:
    max_batch: int = 64
    deadline_ms: float = 2.0  # dynamic batching deadline
    # shard query batches over the learner's (dp, tp) mesh (replicated
    # params, leading axis split) when running distributed; forwards/s
    # then scales with chip count
    shard_over_mesh: bool = True
    # for a net the server keeps in SLOTS (it offers `slot_state`:
    # models/minicpm_sala_q.py; runtime/family.server_slots). slots: how
    # many sessions may be live (0: one per env of the actor fleet,
    # and one for the eval worker); slot_max_len: the longest episode
    # a session may declare, and what one that declares nothing gets
    # (0: env.max_episode_frames + 1); slot_pool_tokens: positions of
    # keys and values the shared pool holds (0: slots x slot_max_len);
    # prefill_chunk: tokens a row of the second bucket kind (a request
    # whose `obs` is [rows, prefill_chunk]); prefill_rows: the most
    # rows of that kind in one dispatch
    slots: int = 0
    slot_max_len: int = 0
    slot_pool_tokens: int = 0
    prefill_chunk: int = 2_048
    prefill_rows: int = 8


@dataclass(frozen=True)
class ServingConfig:
    """Multi-tenant serving tier (parallel/inference_server.py,
    MultiPolicyInferenceServer). Off by default: drivers then build the
    single-tenant BatchedInferenceServer exactly as before. On, every
    policy registers into one continuous-batching tier — per-policy
    epoch-versioned params, priority-class admission, load-shedding,
    and per-tenant serve/<tenant>/ SLO gauges."""

    # route inference through the multi-tenant tier (drivers register
    # their policy under env.id; actor hosts tag wire hellos with it)
    multi_tenant: bool = False
    # admission classes; class 0 is the top class and is never shed
    priority_classes: int = 3
    # class that ordinary actor traffic rides in (eval workers and
    # other latency-sensitive callers should use a lower number)
    default_class: int = 1
    # pending-item depth where the admission controller starts
    # shedding lower classes and engages transport backpressure
    # (hysteresis: releases at half this depth)
    queue_slo_items: int = 256
    # per-request admission-queue deadline; an expired request raises
    # ServeDeadlineExceeded naming its policy_id. 0 disables.
    request_deadline_ms: float = 0.0
    # per-tenant serve/<tenant>/ gauge publish cadence
    stats_every_s: float = 1.0
    # coalesce same-family tenants into one stacked/gather-indexed
    # forward (off: mixed batches still work, one dispatch per batch
    # is only guaranteed per single-tenant batch)
    coalesce: bool = True
    # propagate the admission controller's backpressure signal onto
    # the experience transport (SocketTransport.set_backpressure)
    backpressure: bool = True

    def __post_init__(self) -> None:
        if self.priority_classes < 1:
            raise ValueError(
                f"serving.priority_classes must be >= 1 "
                f"(got {self.priority_classes})")
        if not 0 <= self.default_class < self.priority_classes:
            raise ValueError(
                f"serving.default_class must be in "
                f"[0, {self.priority_classes}) "
                f"(got {self.default_class})")
        if self.queue_slo_items < 1:
            raise ValueError(
                f"serving.queue_slo_items must be >= 1 "
                f"(got {self.queue_slo_items})")


@dataclass(frozen=True)
class ParallelConfig:
    dp: int = 1  # data-parallel (ICI) learner shards
    tp: int = 1  # tensor-parallel shards for dense layers


@dataclass(frozen=True)
class CommConfig:
    """Cross-host experience/param transport (comm/socket_transport).

    wire_codec: per-leaf experience compression on the ingest wire —
    "delta-deflate" (default) ships uint8 frame leaves as XOR-delta vs
    the previous row + zlib deflate, bit-packs bools, deflates small
    ints, leaves floats raw; "raw" is the escape hatch (and what either
    peer silently degrades to when the other side predates the codec —
    negotiation happens per connection, see MSG_HELLO in
    comm/socket_transport.py). The ingest wire is the #1 measured live
    bottleneck (PERF.md round-4: 10.5 MB/s, ~9.7KB/transition), so the
    default is on."""

    wire_codec: str = "delta-deflate"
    # Param-plane codec (comm/param_codec.py): "delta-q8" (default)
    # ships params as per-leaf int8-quantized deltas vs the version the
    # peer last received, with per-leaf and whole-payload never-inflate
    # guards and automatic full resync on missed versions / epoch
    # bumps; "raw" is the escape hatch keeping the TCP param path
    # bitwise identical to the pre-codec build. Negotiated per channel
    # (hello offer for pushes, a request field for pulls), so either
    # peer predating the codec degrades silently to raw. Only the
    # actor-side policy copy rides this — optimizer state never crosses
    # this wire (PARITY.md pins the quantized-policy tolerance).
    param_codec: str = "delta-q8"
    # How many encoded delta segments the learner keeps for catch-up:
    # a peer further behind than this many publishes gets a full resync
    # instead of a delta chain.
    param_delta_window: int = 8
    # Supervised reconnect (SocketTransport): capped jittered
    # exponential backoff between reconnect attempts after the
    # experience connection fails. The cap MUST stay below the
    # server's idle_grace_s (5.0) — a backing-off fleet retries inside
    # every quiesce grace window, so a learner blip never reads as
    # "all producers gone" (see SocketIngestServer.quiesced).
    reconnect_base_s: float = 0.05
    reconnect_cap_s: float = 2.0
    # Offer the server-initiated param publication capability in the
    # hello (MSG_PARAMS_PUSH): params arrive at publish boundaries
    # instead of on the poll cadence. Off by default — the poll path
    # is the universally-interoperable one; against a pre-push learner
    # the offer is silently ignored either way.
    params_push: bool = False
    # Same-host shared-memory transport (comm/shm_transport.py):
    # experience packs straight into a per-connection shm ring
    # (MSG_SHM_DOORBELL names slots on the existing TCP socket) and
    # params read from one seqlock area, engaging only when the hello's
    # boot-id + namespace probe proves same-host. Off by default: the
    # TCP paths are bitwise unchanged when disabled, and every shm
    # failure mode (old peer, cross-host, full ring, torn read)
    # degrades to them anyway. shm=True on BOTH learner (grant) and
    # actor host (offer) sides engages it.
    shm: bool = False
    # per-connection experience ring geometry: slot count and bytes
    # per slot (a batch outsizing a slot falls back to TCP, counted in
    # shm_fallbacks). The learner side caps what an actor may request.
    shm_slots: int = 8
    shm_slot_bytes: int = 1 << 22
    # seqlock param area capacity (learner side, one area shared by
    # every granted client); an oversize pickled param blob publishes
    # a marker instead and readers fall back to the TCP param path
    shm_param_bytes: int = 1 << 26


@dataclass(frozen=True)
class ObsConfig:
    """Observability layer (ape_x_dqn_tpu/obs): span tracing, metric
    registry, heartbeat stall watchdog. Disabled by default — the
    runtime then routes every obs call through the no-op NullObs, so
    the hot loops carry ~zero instrumentation overhead (the learner
    jits are never touched either way)."""

    enabled: bool = False
    # Chrome/Perfetto trace_event JSON output path ("" = no trace file;
    # spans still aggregate into the JSONL stage-time breakdown).
    # Load in chrome://tracing or https://ui.perfetto.dev.
    trace_path: str = ""
    # bounded span buffer: beyond this, events still count toward the
    # stage aggregates but drop from the trace file (memory cap)
    trace_max_events: int = 200_000
    # publish cadence for the registry -> JSONL snapshot (grad-steps);
    # drivers also publish once at shutdown
    publish_every_steps: int = 500
    # heartbeat watchdog: a component (actor-i / ingest / learner /
    # inference-server) silent this long makes the driver raise an
    # attributed StallError instead of hanging. Must exceed the longest
    # legitimate gap (a cold inference-server bucket compile can hold
    # actors for 10-40s on TPU; the 60s query timeout bounds it).
    # 0 disables the watchdog.
    heartbeat_timeout_s: float = 120.0
    # opt-in jax.profiler window (XLA-level twin of the span trace):
    # trace this many grad-steps into jax_profile_dir starting at the
    # first training dispatch ("" = off)
    jax_profile_dir: str = ""
    jax_profile_steps: int = 24
    # log each warmed jit's XLA memory_analysis() into the JSONL
    # (hbm/<jit>/<field> keys — the measured anchors utils/hbm.py's
    # static budget calibrates against)
    hbm_dump: bool = True
    # fleet telemetry cadence (obs/fleet.py): remote actor hosts ship
    # a MSG_TELEMETRY snapshot frame this often; the learner-side
    # aggregator merges them into the run JSONL under peer/<id>/ keys
    # and re-beats remote heartbeats into the stall watchdog. 0
    # disables the emitter thread (frames also require both wire ends
    # to negotiate the capability — an old peer degrades to none).
    telemetry_every_s: float = 2.0
    # -- continuous perf plane (obs/profiling.py, ISSUE 8) --------------
    # live roofline gauges (per-stage mfu / hbm_bw_frac / device_ms):
    # default ON with obs, and they touch no jit. A gauge is published
    # from a block_until_ready-bracketed window around its stage: the
    # single-process trainer brackets every stage when obs is on; the
    # threaded driver (runtime/driver.py) brackets nothing by default,
    # so its train/ingest gauges appear only with profile_windows
    profile_gauges: bool = True
    # sampled windows on the threaded driver's async paths (the
    # learner's train dispatch and the zero-copy ingest ship): default
    # OFF — enabling brackets every profile_window_every-th dispatch
    # and ship with a block_until_ready, made after the state lock is
    # released, trading a sliver of pipeline overlap for honest device
    # time per stage
    profile_windows: bool = False
    profile_window_every: int = 16
    # jit-compile interceptor (jit_compiles / jit_compile_ms counters
    # + the cumulative compile_cache_entries gauge that monitors the
    # XLA accumulation regime run_chunked.sh works around)
    compile_telemetry: bool = True
    # EWMA perf-regression engine: a rate window below perf_frac of
    # its rolling baseline logs an attributed PerfDegradation event
    # (warn-only — never raises, unlike the stall watchdog)
    perf_regression: bool = True
    perf_frac: float = 0.5
    perf_ewma_alpha: float = 0.1
    perf_min_samples: int = 8
    perf_cooldown_s: float = 30.0
    # -- learning-health plane (obs/learning.py, ISSUE 10) --------------
    # warn-only anomaly engine over the in-graph learner diagnostics
    # (loss spikes vs an EWMA baseline + absolute rules for Q blowup,
    # ESS collapse, dead gradients, priority collapse — thresholds in
    # obs/learning.py, mirrored by obs/report.py healthy ranges). The
    # learn_* gauges themselves ride the learner's metrics pytree and
    # are published whenever obs is enabled; this knob only gates the
    # event engine.
    learn_health: bool = True
    learn_spike_mult: float = 10.0
    learn_ewma_alpha: float = 0.2
    learn_min_samples: int = 8
    learn_cooldown_s: float = 30.0
    # MFU / bandwidth-fraction denominators; 0 = from the device_kind
    # table (obs/profiling.device_peaks). A device the table does not
    # know publishes no mfu_* / hbm_bw_frac_* gauges unless set here
    device_peak_flops: float = 0.0
    device_peak_bytes_per_s: float = 0.0
    # -- forensics plane (obs/blackbox.py, ISSUE 17) --------------------
    # per-process flight recorder: fixed-size ring of attributed
    # events, dumped to blackbox-<peer>.json on crash / StallError /
    # SIGUSR2 / supervisor request. blackbox_dir="" puts dumps next to
    # the run JSONL (cwd when metrics are in-memory).
    blackbox: bool = True
    blackbox_dir: str = ""
    blackbox_capacity: int = 512
    blackbox_log_lines: int = 64


@dataclass(frozen=True)
class RemediationConfig:
    """Fleet remediation plane (runtime/remediation.py): the policy
    engine that closes the monitor->actuator loop inside the driver's
    supervisor tick. Off by default — the engine is then never
    constructed and the supervisor path is bitwise the pre-remediation
    one. "observe" dry-runs every rule (attributed JSONL `remediation`
    events with outcome=observed, counters, gauges) without ever
    calling an actuator; "enforce" acts."""

    mode: str = "off"  # off | observe | enforce
    # consecutive supervisor ticks a gauge rule (queue-SLO breach,
    # ingest-drop pressure) must agree before its actuator moves, and
    # again before it moves back — a sensor flapping breach/clear every
    # tick never accumulates a streak, so actuators cannot oscillate
    hysteresis_ticks: int = 3
    # event rules (peer perf degradation, tenant learning degradation)
    # fire after this many attributed events on one target inside the
    # sliding window — one noisy sample is not a policy decision
    event_threshold: int = 2
    event_window_s: float = 120.0
    # per-(target, action) cooldown: the same remedy is not re-applied
    # to the same target faster than this
    cooldown_s: float = 60.0
    # global token-bucket budget for NON-safety actions (backpressure,
    # autoscale, priority re-temper) in actions/minute; safety actions
    # (restart of a wedged local slot, quarantine of a stalled peer)
    # bypass the bucket — suppressing them would leave a stale
    # heartbeat for the watchdog to escalate into a run-fatal
    # StallError, strictly worse than acting
    budget_per_min: float = 6.0
    # quiet period after which engaged remedies are unwound: a boosted
    # tenant priority reverts to serving.default_class, a paused actor
    # slot resumes, a client-side backpressure flag with a dead
    # controller is released
    release_after_s: float = 300.0
    # autoscale floor: the ingest-pressure rule never pauses the fleet
    # below this many running local actor slots
    min_actors: int = 1

    def __post_init__(self) -> None:
        if self.mode not in ("off", "observe", "enforce"):
            raise ValueError(
                f"remediation.mode must be off | observe | enforce "
                f"(got {self.mode!r})")
        if self.hysteresis_ticks < 1:
            raise ValueError(
                f"remediation.hysteresis_ticks must be >= 1 "
                f"(got {self.hysteresis_ticks})")
        if self.event_threshold < 1:
            raise ValueError(
                f"remediation.event_threshold must be >= 1 "
                f"(got {self.event_threshold})")
        if self.budget_per_min <= 0:
            raise ValueError(
                f"remediation.budget_per_min must be > 0 "
                f"(got {self.budget_per_min})")
        if self.min_actors < 0:
            raise ValueError(
                f"remediation.min_actors must be >= 0 "
                f"(got {self.min_actors})")


@dataclass(frozen=True)
class RunConfig:
    name: str = "cartpole_smoke"
    seed: int = 0
    total_env_frames: int = 200_000
    env: EnvConfig = field(default_factory=EnvConfig)
    network: NetworkConfig = field(default_factory=NetworkConfig)
    replay: ReplayConfig = field(default_factory=ReplayConfig)
    learner: LearnerConfig = field(default_factory=LearnerConfig)
    actors: ActorConfig = field(default_factory=ActorConfig)
    inference: InferenceConfig = field(default_factory=InferenceConfig)
    # multi-tenant serving tier (off = single-tenant server, bitwise
    # the pre-tier path); enable with --set serving.multi_tenant=true
    serving: ServingConfig = field(default_factory=ServingConfig)
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    comm: CommConfig = field(default_factory=CommConfig)
    # observability (ape_x_dqn_tpu/obs): off by default; enable with
    # --set obs.enabled=true [--set obs.trace_path=trace.json ...]
    obs: ObsConfig = field(default_factory=ObsConfig)
    # fleet remediation plane (runtime/remediation.py): off by default;
    # dry-run with --set remediation.mode=observe, close the loop with
    # --set remediation.mode=enforce
    remediation: RemediationConfig = field(
        default_factory=RemediationConfig)
    eval_every_steps: int = 10_000
    eval_episodes: int = 10
    eval_eps: float = 0.001
    # Per-episode frame cap for the periodic/final eval. The Atari
    # protocol's 108k (30 min of game time) is right for real ALE runs;
    # short runs and hosts where each eval env-step is expensive can
    # bound it — an uncapped episode once left the 57-game rotation
    # unable to finish a single eval while training saturated the
    # device.
    eval_max_frames: int = 108_000
    # Wall-clock budget for the END-OF-RUN eval backstop (the greedy
    # eval the driver guarantees when a run finishes without a periodic
    # eval having completed). A hard-coded 60s once returned no eval
    # where 5 episodes x 2000 steps took ~300s — a fully-trained suite
    # game then recorded eval=null and was discarded.
    final_eval_deadline_s: float = 600.0
    checkpoint_dir: str = ""
    checkpoint_every: int = 50_000
    # Opt-in, SINGLE-HOST driver only (the multihost driver rejects it:
    # its replicated payload gather would multiply the save by dp x
    # capacity): include the device ReplayState (storage + sum-tree +
    # cursors) in checkpoints. Resume then skips the min_fill refill
    # stall and keeps the replay distribution continuous across a
    # preemption (SURVEY.md §5 "and (optionally) replay contents").
    # The flag governs SAVES; restores follow what the checkpoint
    # contains, so toggling it between runs cannot brick resume.
    # Cost scales with capacity — the flagship's 2M-transition
    # frame-ring is ~20GB per save plus a transient on-device copy, so
    # the default stays off; at Pong-scale capacities it is cheap
    # (measured: see PERF.md "Replay-contents checkpointing").
    checkpoint_replay: bool = False
    # JAX profiler capture (SURVEY.md §5 tracing/profiling): when set,
    # the driver traces `profile_steps` learner grad-steps starting at
    # the first dispatch after min-fill into this directory
    # (TensorBoard/Perfetto-readable)
    profile_dir: str = ""
    profile_steps: int = 24
    # Multihost stall watchdog (runtime/multihost_driver.StallWatchdog):
    # seconds of zero round progress before a host-local diagnostic
    # fires naming this process; two consecutive silent windows abort
    # the process so the job restarts from the latest checkpoint
    # instead of hanging in a dead peer's collective. 0 disables.
    # Must exceed the slowest legitimate in-loop operation (first-round
    # XLA compiles when AOT warmup is unavailable, checkpoint gathers
    # over slow links).
    multihost_watchdog_s: float = 300.0

    def replace(self, **kw: Any) -> "RunConfig":
        return dataclasses.replace(self, **kw)


def _preset_cartpole_smoke() -> RunConfig:
    """Config 1: CartPole-v1 single-actor DQN, MLP, uniform replay (CPU smoke)."""
    return RunConfig(
        name="cartpole_smoke",
        total_env_frames=120_000,
        env=EnvConfig(id="CartPole-v1", kind="cartpole"),
        network=NetworkConfig(kind="mlp", mlp_hidden=(256, 256), dueling=False,
                              compute_dtype="float32"),
        replay=ReplayConfig(kind="uniform", capacity=50_000, min_fill=1_000),
        learner=LearnerConfig(batch_size=64, lr=1e-3, n_step=3,
                              target_sync_every=250),
        actors=ActorConfig(num_actors=1, base_eps=1.0),
    )


def _preset_pong() -> RunConfig:
    """Config 2: PongNoFrameskip-v4, Nature-CNN, 8 actors, prioritized replay."""
    return RunConfig(
        name="pong",
        total_env_frames=10_000_000,
        env=EnvConfig(id="PongNoFrameskip-v4", kind="atari"),
        network=NetworkConfig(kind="nature_cnn", dueling=True),
        # 1M transitions rounds to 2^20 in the drivers; as packed
        # frame-ring byte rows that is 9.63GiB + model/opt + ~2GiB
        # transient headroom = ~11.7GiB on one 16GiB chip (verified by
        # compiled memory stats AND a full-capacity bench run — PERF.md
        # "HBM budget"; the driver's check_hbm_fits re-prices it at
        # startup)
        replay=ReplayConfig(kind="prioritized", capacity=1_000_000,
                            min_fill=20_000, storage="frame_ring"),
        # steps_per_frame_cap pins the Ape-X effective replay ratio
        # (Horgan et al. 2018: ~19 grad-steps/s at batch 512 against
        # ~12.5k ingested transitions/s = ~0.78 samples/insert, i.e.
        # ~1.6e-3 grad-steps per ingested env step). Without it the
        # 490/s TPU learner free-runs hundreds of epochs over a slow
        # actor fleet's replay — the pathology PERF.md measured live.
        # sample_chunk=4: K-batch sampling relaxation, +4% on the real
        # chip with learning parity on the catch e2e (PERF.md "K-batch
        # sampling").
        learner=LearnerConfig(batch_size=512, steps_per_frame_cap=1.6e-3,
                              sample_chunk=4),
        actors=ActorConfig(num_actors=8, envs_per_actor=16),
    )


def _preset_atari57_apex() -> RunConfig:
    """Config 3: full Ape-X over the 57-game ALE suite, 256 actors."""
    return RunConfig(
        name="atari57_apex",
        total_env_frames=22_500_000_000,
        env=EnvConfig(id="atari57", kind="atari"),
        network=NetworkConfig(kind="nature_cnn", dueling=True),
        # frame-ring storage: the attested ~2M-transition capacity only
        # fits in HBM as single frames (~10KB/transition vs ~56KB flat)
        replay=ReplayConfig(kind="prioritized", capacity=2_000_000,
                            storage="frame_ring"),
        # replay-ratio pin + vector actors + K-batch sampling: see the
        # pong preset notes (the dist learner implements the same
        # sample_chunk relaxation per shard). 256 actor threads x 16
        # envs = 4096 env slots across the remote actor hosts; each
        # thread ships one 16-item inference query per vector step
        # (runtime/actor.py)
        learner=LearnerConfig(batch_size=512, steps_per_frame_cap=1.6e-3,
                              sample_chunk=4),
        actors=ActorConfig(num_actors=256, envs_per_actor=16),
        parallel=ParallelConfig(dp=4, tp=2),
    )


def _preset_r2d2() -> RunConfig:
    """Config 4: recurrent LSTM Q-net with stored-state sequence replay."""
    return RunConfig(
        name="r2d2",
        total_env_frames=10_000_000_000,
        env=EnvConfig(id="atari57", kind="atari"),
        network=NetworkConfig(kind="lstm_q", dueling=True),
        # frame_ring: a sequence stores its 83 single frames as 83 byte
        # rows of 7,168 B (594,944 B at L=80; per-step stacks would be
        # 2.2 MB) — one row per frame, because a TPU gather fetches a
        # row whole only up to 32,640 B (replay/packing.py::row_layout).
        # Capacity is HBM-budgeted (utils/hbm.py): 65536 sequences over
        # dp=4 shards = 16384/shard x 0.60MB = 9.16GiB per 16GiB chip
        # (~2.6M transitions fleet-wide at overlap 40 — above the
        # attested ~2M-transition replay scale); measured on one v5e at
        # dp=1, capacity 16384: peak HBM 9.67 GiB with the train step's
        # temps (PERF.md §5, PR 26). R2D2-paper 100k+ sequences: raise
        # dp to 8 (--set parallel.dp=8) or run 32GiB-HBM chips; the
        # driver's check_hbm_fits prints the budget table if a layout
        # doesn't fit.
        replay=ReplayConfig(kind="sequence", capacity=65_536,  # sequences
                            seq_length=80, seq_overlap=40, burn_in=40,
                            min_fill=5_000, storage="frame_ring"),
        # sample_chunk=4: the K-batch sampling relaxation, adopted for
        # sequences in round 5 on a +25% grad-steps/s A/B (52.5 -> 66)
        # taken on the pre-PR-1 rig, behind a slow host<->device link
        # that no longer exists and in a storage layout whose gather
        # copied the whole replay per draw; on the v5e this preset at
        # dp=1 runs 91.85 grad-steps/s with K=4 (PERF.md §5, PR 26; K=1
        # has not been timed there). Learning parity on the
        # masked-CartPole POMDP e2e (K=1 eval 43.2 vs K=4 42.7, both
        # >35 bar) is a CPU result and stands.
        learner=LearnerConfig(batch_size=64, n_step=5, value_rescale=True,
                              target_sync_every=2500, lr=1e-4,
                              sample_chunk=4),
        # vectorized recurrent actors: one {obs,c,h} query of 16 envs
        # per vector step (runtime/actor.py:RecurrentActor)
        actors=ActorConfig(num_actors=256, envs_per_actor=16),
        parallel=ParallelConfig(dp=4, tp=2),
    )


def _preset_apex_dpg() -> RunConfig:
    """Config 5: Ape-X DPG continuous control (DM Control humanoid class)."""
    return RunConfig(
        name="apex_dpg",
        total_env_frames=100_000_000,
        env=EnvConfig(id="humanoid_stand", kind="control"),
        network=NetworkConfig(kind="dpg", compute_dtype="float32"),
        replay=ReplayConfig(kind="prioritized", capacity=1_000_000,
                            min_fill=10_000),
        learner=LearnerConfig(batch_size=256, n_step=5, gamma=0.99),
        actors=ActorConfig(num_actors=32),
    )


def _preset_glm47_flash_q() -> RunConfig:
    """Config 6: GLM-4.7-Flash as a token-level Q-network (ILQL's form,
    Snell et al. 2022, run the Ape-X way): observation = the token
    sequence so far, action = the next token, Q(s_t, .) = the decoder's
    own head. The sizes are the model's config.json
    (https://huggingface.co/zai-org/GLM-4.7-Flash, model_type
    glm4_moe_lite): 47 layers, 64 routed experts, 154,880 vocabulary
    rows — 30 B parameters, which at this learner's 16 B a parameter
    no chip holds: a run gives one chip its share with
    network.glm.shard_count / num_hidden_layers and env.num_tokens
    (benchmarks/configs/glm47_flash_ep8_1chip.json is the measured
    one; check_hbm_fits refuses the preset as it stands). The learner
    settings are this repo's: no model card gives them."""
    glm = GlmMoeConfig()
    return RunConfig(
        name="glm47_flash_q",
        total_env_frames=10_000_000_000,
        env=EnvConfig(id="tokens", kind="synthetic_tokens",
                      num_tokens=glm.vocab_size),
        network=NetworkConfig(kind="glm_moe_q", dueling=False, glm=glm),
        # a stored sequence is 512 tokens: 128 of burn-in, whose latent
        # cache the trained 384 attend to without gradient; no state is
        # stored with it (runtime/family.py). 65,536 of them are 0.7 GB
        replay=ReplayConfig(kind="sequence", capacity=65_536,
                            seq_length=512, seq_overlap=256, burn_in=128,
                            min_fill=2_048),
        # batch 16 sequences = 8,192 tokens a step; a step is ~0.26 s of
        # matmuls, so a dispatch is two steps and the K-batch draw
        # (which amortises a per-step tree round-trip) is off. The
        # optimizer is Ape-X's (Adam 1e-4 / eps 1.5e-7, clip 40)
        learner=LearnerConfig(batch_size=16, n_step=5, value_rescale=True,
                              target_sync_every=2500, lr=1e-4,
                              sample_chunk=1, train_chunk=2),
        actors=ActorConfig(num_actors=64, envs_per_actor=16),
        inference=InferenceConfig(max_batch=16, deadline_ms=2.0),
    )


def _preset_glm_tiny_q() -> RunConfig:
    """glm47_flash_q's sibling for CPU tests: the same decoder at
    hidden 64 with 8 experts and a vocabulary of 64, float32."""
    glm = GlmMoeConfig(
        hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
        num_hidden_layers=3, num_attention_heads=2, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2,
        vocab_size=64)
    return RunConfig(
        name="glm_tiny_q",
        total_env_frames=100_000,
        env=EnvConfig(id="tokens", kind="synthetic_tokens",
                      num_tokens=glm.vocab_size),
        network=NetworkConfig(kind="glm_moe_q", dueling=False, glm=glm,
                              compute_dtype="float32"),
        replay=ReplayConfig(kind="sequence", capacity=64, seq_length=16,
                            seq_overlap=8, burn_in=4, min_fill=8),
        learner=LearnerConfig(batch_size=4, n_step=3, value_rescale=True,
                              target_sync_every=100, lr=1e-3,
                              sample_chunk=1, train_chunk=2),
        actors=ActorConfig(num_actors=1, envs_per_actor=2),
        inference=InferenceConfig(max_batch=8, deadline_ms=2.0),
    )


def _preset_trinity_mini_q() -> RunConfig:
    """Config 7: Trinity-Mini (Arcee, 26B-A3B) as a token-level
    Q-network, the decoder family's second net. The sizes are the
    model's config.json
    (https://huggingface.co/arcee-ai/Trinity-Mini, model_type afmoe):
    32 layers (3 sliding-window : 1 full attention, window 2,048), 128
    routed experts of 1,024, top-8, 200,192 vocabulary rows. Whole it
    is 26 B parameters and check_hbm_fits refuses it: a run gives one
    chip its share with network.afmoe.shard_count / num_hidden_layers /
    layer_types and env.num_tokens
    (benchmarks/configs/trinity_mini_ep16_1chip.json is the measured
    one). The learner settings are this repo's: sequences of 8,192
    tokens, so that the 2,048-token window and the full layers differ."""
    afmoe = AfmoeConfig()
    return RunConfig(
        name="trinity_mini_q",
        total_env_frames=10_000_000_000,
        env=EnvConfig(id="tokens", kind="synthetic_tokens",
                      num_tokens=afmoe.vocab_size),
        network=NetworkConfig(kind="afmoe_q", dueling=False, afmoe=afmoe),
        # a stored sequence is 8,192 tokens: 2,048 of burn-in, whose
        # keys and values the trained 6,144 attend to without gradient
        # (a sliding layer keeps the last 2,047 of them). 4,096
        # sequences are GLM's token count, 0.63 GiB
        replay=ReplayConfig(kind="sequence", capacity=4_096,
                            seq_length=8_192, seq_overlap=4_096,
                            burn_in=2_048, min_fill=128),
        # batch 2 sequences = 16,384 tokens a step
        learner=LearnerConfig(batch_size=2, n_step=5, value_rescale=True,
                              target_sync_every=2500, lr=1e-4,
                              sample_chunk=1, train_chunk=2),
        # a query re-runs a window of up to 8,192 tokens (the family's
        # stateless protocol): one at a time
        actors=ActorConfig(num_actors=64, envs_per_actor=1),
        inference=InferenceConfig(max_batch=1, deadline_ms=2.0),
    )


def _preset_trinity_tiny_q() -> RunConfig:
    """trinity_mini_q's sibling for CPU tests: the same decoder at
    hidden 64, 4 query / 2 key-value heads of 16, 8 experts, a
    vocabulary of 64 and a window of 8 inside sequences of 32, float32."""
    afmoe = AfmoeConfig(
        hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
        num_hidden_layers=3, num_dense_layers=1, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16,
        layer_types=("sliding_attention", "sliding_attention",
                     "full_attention"),
        sliding_window=8, num_experts=8, num_experts_per_tok=2,
        vocab_size=64)
    return RunConfig(
        name="trinity_tiny_q",
        total_env_frames=100_000,
        env=EnvConfig(id="tokens", kind="synthetic_tokens",
                      num_tokens=afmoe.vocab_size),
        network=NetworkConfig(kind="afmoe_q", dueling=False, afmoe=afmoe,
                              compute_dtype="float32"),
        replay=ReplayConfig(kind="sequence", capacity=64, seq_length=32,
                            seq_overlap=16, burn_in=12, min_fill=8),
        learner=LearnerConfig(batch_size=4, n_step=3, value_rescale=True,
                              target_sync_every=100, lr=1e-3,
                              sample_chunk=1, train_chunk=2),
        actors=ActorConfig(num_actors=1, envs_per_actor=2),
        inference=InferenceConfig(max_batch=8, deadline_ms=2.0),
    )


def _preset_smallthinker_21b_q() -> RunConfig:
    """Config 8: SmallThinker-21BA3B-Instruct (PowerInfer, 21B-A3B) as
    a token-level Q-network, the decoder family's third net. The sizes
    are the model's config.json
    (https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct):
    52 layers (one global layer without position encoding, then three
    with RoPE and a 4,096 window), 64 routed ReGLU experts of 768,
    top-6, no shared expert, 151,936 vocabulary rows. Whole it is 21 B
    parameters and check_hbm_fits refuses it: a run gives one chip its
    share with network.smallthinker.shard_count / num_hidden_layers /
    the two layouts and env.num_tokens
    (benchmarks/configs/smallthinker_21b_ep8_1chip.json is the measured
    one). The learner settings are this repo's: sequences of 16,384
    tokens, the model's whole context, so that the 4,096 window saves
    more than half of a sliding layer's pairs."""
    st = SmallThinkerConfig()
    return RunConfig(
        name="smallthinker_21b_q",
        total_env_frames=10_000_000_000,
        env=EnvConfig(id="tokens", kind="synthetic_tokens",
                      num_tokens=st.vocab_size),
        network=NetworkConfig(kind="smallthinker_q", dueling=False,
                              smallthinker=st),
        # a stored sequence is 16,384 tokens: 4,096 of burn-in, whose
        # keys and values the trained 12,288 attend to without gradient
        # (a sliding layer keeps the last 4,095 of them). 2,048
        # sequences are the other decoders' token count, 0.63 GiB
        replay=ReplayConfig(kind="sequence", capacity=2_048,
                            seq_length=16_384, seq_overlap=8_192,
                            burn_in=4_096, min_fill=64),
        # batch 1: one sequence is the 16,384 tokens a step
        learner=LearnerConfig(batch_size=1, n_step=5, value_rescale=True,
                              target_sync_every=2500, lr=1e-4,
                              sample_chunk=1, train_chunk=2),
        # a query re-runs a window of up to 16,384 tokens (the family's
        # stateless protocol): one at a time
        actors=ActorConfig(num_actors=64, envs_per_actor=1),
        inference=InferenceConfig(max_batch=1, deadline_ms=2.0),
    )


def _preset_smallthinker_tiny_q() -> RunConfig:
    """smallthinker_21b_q's sibling for CPU tests: the same decoder at
    hidden 64, 7 query heads to each of 2 key-value heads of 16, 8
    experts top-3, a vocabulary of 64 and a window of 8 inside
    sequences of 32, one period of four layers, float32."""
    st = SmallThinkerConfig(
        hidden_size=64, num_hidden_layers=4, num_attention_heads=14,
        num_key_value_heads=2, head_dim=16, moe_ffn_hidden_size=32,
        moe_num_primary_experts=8, moe_num_active_primary_experts=3,
        rope_layout=(0, 1, 1, 1), sliding_window_layout=(0, 1, 1, 1),
        sliding_window_size=8, max_position_embeddings=32, vocab_size=64)
    return RunConfig(
        name="smallthinker_tiny_q",
        total_env_frames=100_000,
        env=EnvConfig(id="tokens", kind="synthetic_tokens",
                      num_tokens=st.vocab_size),
        network=NetworkConfig(kind="smallthinker_q", dueling=False,
                              smallthinker=st, compute_dtype="float32"),
        replay=ReplayConfig(kind="sequence", capacity=64, seq_length=32,
                            seq_overlap=16, burn_in=12, min_fill=8),
        learner=LearnerConfig(batch_size=4, n_step=3, value_rescale=True,
                              target_sync_every=100, lr=1e-3,
                              sample_chunk=1, train_chunk=2),
        actors=ActorConfig(num_actors=1, envs_per_actor=2),
        inference=InferenceConfig(max_batch=8, deadline_ms=2.0),
    )


def _preset_ouro_2p6b_q() -> RunConfig:
    """Config 9: Ouro-2.6B (ByteDance, a looped language model) as a
    token-level Q-network, the decoder family's fourth net and its
    first without experts. The sizes are the model's config.json
    (https://huggingface.co/ByteDance/Ouro-2.6B): 48 blocks of full
    attention (16 heads of 128, ungrouped) and a SwiGLU MLP of 5,632,
    the whole stack run `total_ut_steps` = 4 times with the same
    weights, 49,152 vocabulary rows. Whole it is 2.67 B parameters =
    39.8 GiB at the learner's 16 B and check_hbm_fits refuses it: a run
    gives one chip its pipeline stage with
    network.ouro.num_hidden_layers
    (benchmarks/configs/ouro_2p6b_1chip.json is the measured one). The
    learner settings are this repo's: sequences of 4,096 tokens, the
    model's own pre-training length, where float32 Q over the whole
    vocabulary still fits."""
    ou = OuroConfig()
    return RunConfig(
        name="ouro_2p6b_q",
        total_env_frames=10_000_000_000,
        env=EnvConfig(id="tokens", kind="synthetic_tokens",
                      num_tokens=ou.vocab_size),
        network=NetworkConfig(kind="ouro_q", dueling=False, ouro=ou),
        # a stored sequence is 4,096 tokens: 1,024 of burn-in, whose
        # keys and values - one set per (loop step, layer) - the
        # trained 3,072 attend to without gradient. 8,192 sequences are
        # the other decoders' token count, 0.63 GiB
        replay=ReplayConfig(kind="sequence", capacity=8_192,
                            seq_length=4_096, seq_overlap=2_048,
                            burn_in=1_024, min_fill=64),
        # batch 1: float32 Q over 49,152 actions is 0.56 GiB a copy at
        # 3,072 trained tokens
        learner=LearnerConfig(batch_size=1, n_step=5, value_rescale=True,
                              target_sync_every=2500, lr=1e-4,
                              sample_chunk=1, train_chunk=2),
        # a query re-runs a window of up to 4,096 tokens (the family's
        # stateless protocol): one at a time
        actors=ActorConfig(num_actors=64, envs_per_actor=1),
        inference=InferenceConfig(max_batch=1, deadline_ms=2.0),
    )


def _preset_ouro_tiny_q() -> RunConfig:
    """ouro_2p6b_q's sibling for CPU tests: the same looped decoder at
    hidden 64, 4 ungrouped heads of 16, an MLP of 96, a vocabulary of
    64, 2 layers run 4 times, sequences of 32, float32."""
    ou = OuroConfig(
        hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=4, head_dim=16, intermediate_size=96,
        max_position_embeddings=32, vocab_size=64)
    return RunConfig(
        name="ouro_tiny_q",
        total_env_frames=100_000,
        env=EnvConfig(id="tokens", kind="synthetic_tokens",
                      num_tokens=ou.vocab_size),
        network=NetworkConfig(kind="ouro_q", dueling=False, ouro=ou,
                              compute_dtype="float32"),
        replay=ReplayConfig(kind="sequence", capacity=64, seq_length=32,
                            seq_overlap=16, burn_in=12, min_fill=8),
        learner=LearnerConfig(batch_size=4, n_step=3, value_rescale=True,
                              target_sync_every=100, lr=1e-3,
                              sample_chunk=1, train_chunk=2),
        actors=ActorConfig(num_actors=1, envs_per_actor=2),
        inference=InferenceConfig(max_batch=8, deadline_ms=2.0),
    )


def _preset_kimi_linear_48b_q() -> RunConfig:
    """Config 10: Kimi-Linear-48B-A3B-Instruct (Moonshot) as a
    token-level Q-network, the decoder family's fifth net and its first
    with a scan layer that is not an LSTM. The sizes are the model's
    config.json
    (https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct):
    27 layers, three of Kimi Delta Attention (32 heads of 128, a
    128 x 128 float32 state a head) to one of latent attention without
    rotation, a leading dense layer, then 256 routed experts of 1,024,
    top-8, one shared, 163,840 vocabulary rows. Whole it is 48 B
    parameters and check_hbm_fits refuses it: a run gives one chip its
    share with network.kimi_linear.shard_count / vocab_shard_count /
    num_hidden_layers and env.num_tokens
    (benchmarks/configs/kimi_linear_48b_ep32_1chip.json is the measured
    one). The learner settings are this repo's: sequences of 4,096
    tokens, long episodes replayed whole - the job the linear layers
    were built for - at the longest length whose step fits one chip
    beside 602 M parameters' learner state (8,192 does not: the
    configuration file's `memory`)."""
    kl = KimiLinearConfig()
    return RunConfig(
        name="kimi_linear_48b_q",
        total_env_frames=10_000_000_000,
        env=EnvConfig(id="tokens", kind="synthetic_tokens",
                      num_tokens=kl.vocab_size),
        network=NetworkConfig(kind="kimi_linear_q", dueling=False,
                              kimi_linear=kl),
        # a stored sequence is 4,096 tokens: 1,024 of burn-in, which
        # leaves a KDA layer one state matrix a head and three rows of
        # its convolutions however long it is, and an MLA layer a latent
        # row per position; the trained 3,072 start from both without
        # gradient. 8,192 sequences are the other decoders' token
        # count, 0.63 GiB
        replay=ReplayConfig(kind="sequence", capacity=8_192,
                            seq_length=4_096, seq_overlap=2_048,
                            burn_in=1_024, min_fill=64),
        # batch 1: one sequence is the 4,096 tokens a step
        learner=LearnerConfig(batch_size=1, n_step=5, value_rescale=True,
                              target_sync_every=2500, lr=1e-4,
                              sample_chunk=1, train_chunk=2),
        # a query re-runs a window of up to 4,096 tokens (the family's
        # stateless protocol): one at a time
        actors=ActorConfig(num_actors=64, envs_per_actor=1),
        inference=InferenceConfig(max_batch=1, deadline_ms=2.0),
    )


def _preset_kimi_linear_tiny_q() -> RunConfig:
    """kimi_linear_48b_q's sibling for CPU tests: every kind of layer
    (KDA + dense FFN, KDA + experts, MLA + experts, KDA + experts) at
    hidden 48, 3 KDA heads of 8 (so no KDA width is the hidden size over
    the heads), 2 MLA heads of 12 + 4 over values of 8, 8 experts top-2,
    a vocabulary of 64, sequences of 32, float32."""
    kl = KimiLinearConfig(
        hidden_size=48, intermediate_size=96, moe_intermediate_size=24,
        num_hidden_layers=4, full_attn_layers=(3,), linear_num_heads=3,
        linear_head_dim=8, num_attention_heads=2, kv_lora_rank=16,
        qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=8,
        num_experts=8, num_experts_per_token=2, vocab_size=64)
    return RunConfig(
        name="kimi_linear_tiny_q",
        total_env_frames=100_000,
        env=EnvConfig(id="tokens", kind="synthetic_tokens",
                      num_tokens=kl.vocab_size),
        network=NetworkConfig(kind="kimi_linear_q", dueling=False,
                              kimi_linear=kl, compute_dtype="float32"),
        replay=ReplayConfig(kind="sequence", capacity=64, seq_length=32,
                            seq_overlap=16, burn_in=12, min_fill=8),
        learner=LearnerConfig(batch_size=4, n_step=3, value_rescale=True,
                              target_sync_every=100, lr=1e-3,
                              sample_chunk=1, train_chunk=2),
        actors=ActorConfig(num_actors=1, envs_per_actor=2),
        inference=InferenceConfig(max_batch=8, deadline_ms=2.0),
    )


def _preset_lfm2_24b_q() -> RunConfig:
    """Config 11: LFM2-24B-A2B (Liquid AI, `model_type` lfm2_moe) as a
    token-level Q-network, the decoder family's sixth net and its first
    with a convolution mixer and a head that is its own embedding. The
    sizes are the model's config.json
    (https://huggingface.co/LiquidAI/LFM2-24B-A2B): 40 layers, three
    gated short convolutions (two gates around a causal depthwise
    filter of 3 taps) to one of grouped-query attention (32 : 8 heads
    of 64, q/k norms, RoPE), two leading dense layers, then 64 routed
    experts of 1,536, top-4, none shared, 65,536 vocabulary rows. Whole
    it is 24 B parameters and check_hbm_fits refuses it: a run gives
    one chip its share with network.lfm2_moe.shard_count /
    vocab_shard_count / num_hidden_layers / layer_types /
    num_dense_layers and env.num_tokens
    (benchmarks/configs/lfm2_24b_ep8_1chip.json is the measured one).
    The learner settings are this repo's: sequences of 16,384 tokens,
    long episodes replayed whole, where pair work exists in one layer
    of four."""
    lf = Lfm2MoeConfig()
    return RunConfig(
        name="lfm2_24b_q",
        total_env_frames=10_000_000_000,
        env=EnvConfig(id="tokens", kind="synthetic_tokens",
                      num_tokens=lf.vocab_size),
        network=NetworkConfig(kind="lfm2_moe_q", dueling=False,
                              lfm2_moe=lf),
        # a stored sequence is 16,384 tokens: 4,096 of burn-in, which
        # leaves a conv layer TWO ROWS however long it is and an
        # attention layer keys and values per position; the trained
        # 12,288 start from both without gradient. 2,048 sequences are
        # the other decoders' token count, 0.63 GiB
        replay=ReplayConfig(kind="sequence", capacity=2_048,
                            seq_length=16_384, seq_overlap=8_192,
                            burn_in=4_096, min_fill=64),
        # batch 2: 32,768 tokens a step (the configuration file's
        # `memory` has what fits beside the learner's state)
        learner=LearnerConfig(batch_size=2, n_step=5, value_rescale=True,
                              target_sync_every=2500, lr=1e-4,
                              sample_chunk=1, train_chunk=2),
        # a query re-runs a window of up to 16,384 tokens (the family's
        # stateless protocol): one at a time
        actors=ActorConfig(num_actors=64, envs_per_actor=1),
        inference=InferenceConfig(max_batch=1, deadline_ms=2.0),
    )


def _preset_lfm2_tiny_q() -> RunConfig:
    """lfm2_24b_q's sibling for CPU tests: both kinds of layer and both
    kinds of FFN (conv + dense, attention + experts, conv + experts) at
    hidden 32, 4 query heads to each 2 key-value heads of 12 (48: no
    attention width is the hidden size), 8 experts top-2, a vocabulary
    of 64 (off a lane tile of 128; tests also run one on it),
    sequences of 32, float32."""
    lf = Lfm2MoeConfig(
        hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        num_hidden_layers=3, num_dense_layers=1,
        layer_types=("conv", "full_attention", "conv"),
        num_attention_heads=4, num_key_value_heads=2, head_dim=12,
        max_position_embeddings=32, num_experts=8, num_experts_per_tok=2,
        vocab_size=64)
    return RunConfig(
        name="lfm2_tiny_q",
        total_env_frames=100_000,
        env=EnvConfig(id="tokens", kind="synthetic_tokens",
                      num_tokens=lf.vocab_size),
        network=NetworkConfig(kind="lfm2_moe_q", dueling=False,
                              lfm2_moe=lf, compute_dtype="float32"),
        replay=ReplayConfig(kind="sequence", capacity=64, seq_length=32,
                            seq_overlap=16, burn_in=12, min_fill=8),
        learner=LearnerConfig(batch_size=4, n_step=3, value_rescale=True,
                              target_sync_every=100, lr=1e-3,
                              sample_chunk=1, train_chunk=2),
        actors=ActorConfig(num_actors=1, envs_per_actor=2),
        inference=InferenceConfig(max_batch=8, deadline_ms=2.0),
    )


def _preset_minicpm_sala_9b_q() -> RunConfig:
    """Config 12: MiniCPM-SALA (OpenBMB, 9B dense) as a token-level
    Q-network, the decoder family's seventh net and the first the
    inference server keeps IN SLOTS. The sizes are the model's
    config.json (https://huggingface.co/openbmb/MiniCPM-SALA): 32
    layers, 8 of attention over key blocks the data chooses (32 heads of
    128 over 2 key-value heads, no position encoding) to 24 of lightning
    linear attention (32 heads of 128, a 128 x 128 float32 state a
    head), SwiGLU of 16,384, 73,448 vocabulary rows, untied. Whole it
    is 9.48 B parameters and no learner fits it: check_hbm_fits refuses
    the preset as it stands, and a run gives one chip its pipeline
    stage with network.minicpm_sala.num_hidden_layers / mixer_types
    (benchmarks/configs/minicpm_sala_9b_pp4_1chip.json is the measured
    one: eight layers SERVED, not trained). The learner settings are
    this repo's, as Ouro's; a session's context is the whole episode,
    up to inference.slot_max_len positions."""
    sala = MiniCpmSalaConfig()
    return RunConfig(
        name="minicpm_sala_9b_q",
        total_env_frames=10_000_000_000,
        env=EnvConfig(id="tokens", kind="synthetic_tokens",
                      num_tokens=sala.vocab_size, max_episode_frames=4_096),
        network=NetworkConfig(kind="minicpm_sala_q", dueling=False,
                              minicpm_sala=sala),
        replay=ReplayConfig(kind="sequence", capacity=8_192,
                            seq_length=4_096, seq_overlap=2_048,
                            burn_in=1_024, min_fill=64),
        learner=LearnerConfig(batch_size=1, n_step=5, value_rescale=True,
                              target_sync_every=2500, lr=1e-4,
                              sample_chunk=1, train_chunk=2),
        actors=ActorConfig(num_actors=8, envs_per_actor=8),
        inference=InferenceConfig(max_batch=64, deadline_ms=2.0),
    )


def _preset_minicpm_sala_tiny_q() -> RunConfig:
    """minicpm_sala_9b_q's sibling for CPU tests: both kinds of mixer
    (sparse, lightning, lightning, sparse) at hidden 64, 4 heads of 16
    over 1 key-value head, an MLP of 96, a vocabulary of 64; key blocks
    of 8, compressed keys over 4 positions every 2, a local window of
    16, 6 blocks attended, dense up to 32 positions, so that sequences
    of 64 cross `sparse_dense_len`; float32."""
    sala = MiniCpmSalaConfig(
        hidden_size=64, num_hidden_layers=4,
        mixer_types=("minicpm4", "lightning-attn", "lightning-attn",
                     "minicpm4"),
        num_attention_heads=4, num_key_value_heads=1, head_dim=16,
        lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
        intermediate_size=96, dim_model_base=16,
        max_position_embeddings=4_096, vocab_size=64,
        sparse_block_size=8, sparse_kernel_size=4, sparse_kernel_stride=2,
        sparse_init_blocks=1, sparse_window_size=16, sparse_topk=6,
        sparse_dense_len=32)
    return RunConfig(
        name="minicpm_sala_tiny_q",
        total_env_frames=100_000,
        env=EnvConfig(id="tokens", kind="synthetic_tokens",
                      num_tokens=sala.vocab_size, max_episode_frames=64),
        network=NetworkConfig(kind="minicpm_sala_q", dueling=False,
                              minicpm_sala=sala, compute_dtype="float32"),
        replay=ReplayConfig(kind="sequence", capacity=64, seq_length=64,
                            seq_overlap=32, burn_in=24, min_fill=8),
        learner=LearnerConfig(batch_size=4, n_step=3, value_rescale=True,
                              target_sync_every=100, lr=1e-3,
                              sample_chunk=1, train_chunk=2),
        actors=ActorConfig(num_actors=1, envs_per_actor=2),
        inference=InferenceConfig(max_batch=8, deadline_ms=2.0,
                                  prefill_chunk=16, prefill_rows=2),
    )


def _preset_jamba2_3b_q() -> RunConfig:
    """Config 13: AI21-Jamba2-3B (3.03 B dense) as a token-level
    Q-network, the decoder family's eighth net and the second the
    inference server keeps IN SLOTS. The sizes are the model's
    config.json (https://huggingface.co/ai21labs/AI21-Jamba2-3B): 28
    layers, attention (20 heads of 128 over ONE key-value head, no
    position encoding) at layers 7 and 21, Mamba-1 mixers (5,120
    channels, a state of 16 a channel, a step of rank 160, conv4)
    elsewhere, SwiGLU of 8,192 on every layer, 65,536 vocabulary rows,
    tied. No learner fits it on one chip (one period of 14 layers with
    an eighth of the vocabulary is 21.6 GiB at the learner's 16 B a
    parameter, and no width may be cut): check_hbm_fits refuses the
    preset as a TRAINING run, and it is SERVED whole
    (benchmarks/configs/jamba2_3b_1chip.json: 5.64 GiB in bfloat16, 256
    sessions of 8.89 MiB of state at any context beside 1 KiB a
    position of keys and values). The server settings below are that
    deployment's: 128-row steps for 256 live sessions. The learner
    settings are this repo's, as Ouro's."""
    jamba = JambaConfig()
    return RunConfig(
        name="jamba2_3b_q",
        total_env_frames=10_000_000_000,
        env=EnvConfig(id="tokens", kind="synthetic_tokens",
                      num_tokens=jamba.vocab_size, max_episode_frames=4_096),
        network=NetworkConfig(kind="jamba_q", dueling=False, jamba=jamba),
        replay=ReplayConfig(kind="sequence", capacity=8_192,
                            seq_length=4_096, seq_overlap=2_048,
                            burn_in=1_024, min_fill=64),
        learner=LearnerConfig(batch_size=1, n_step=5, value_rescale=True,
                              target_sync_every=2500, lr=1e-4,
                              sample_chunk=1, train_chunk=2),
        actors=ActorConfig(num_actors=32, envs_per_actor=8),
        inference=InferenceConfig(
            max_batch=128, deadline_ms=2.0, slots=256, slot_max_len=10_240,
            slot_pool_tokens=2_097_152, prefill_chunk=2_048,
            prefill_rows=8),
    )


def _preset_jamba2_tiny_q() -> RunConfig:
    """jamba2_3b_q's sibling for CPU tests: six layers with attention at
    layers 1 and 4 (`i % 3 == 1`), hidden 64, 4 heads of 16 over 1
    key-value head, Mamba mixers of 128 channels with a state of 4 and
    a step of rank 8, an MLP of 96, a vocabulary of 64; float32."""
    jamba = JambaConfig(
        hidden_size=64, num_hidden_layers=6, attn_layer_offset=1,
        attn_layer_period=3, num_attention_heads=4, num_key_value_heads=1,
        intermediate_size=96, mamba_d_state=4, mamba_dt_rank=8,
        max_position_embeddings=4_096, vocab_size=64)
    return RunConfig(
        name="jamba2_tiny_q",
        total_env_frames=100_000,
        env=EnvConfig(id="tokens", kind="synthetic_tokens",
                      num_tokens=jamba.vocab_size, max_episode_frames=64),
        network=NetworkConfig(kind="jamba_q", dueling=False, jamba=jamba,
                              compute_dtype="float32"),
        replay=ReplayConfig(kind="sequence", capacity=64, seq_length=64,
                            seq_overlap=32, burn_in=24, min_fill=8),
        learner=LearnerConfig(batch_size=4, n_step=3, value_rescale=True,
                              target_sync_every=100, lr=1e-3,
                              sample_chunk=1, train_chunk=2),
        actors=ActorConfig(num_actors=1, envs_per_actor=2),
        inference=InferenceConfig(max_batch=8, deadline_ms=2.0,
                                  prefill_chunk=16, prefill_rows=2),
    )


PRESETS = {
    "cartpole_smoke": _preset_cartpole_smoke,
    "pong": _preset_pong,
    "atari57_apex": _preset_atari57_apex,
    "r2d2": _preset_r2d2,
    "apex_dpg": _preset_apex_dpg,
    "glm47_flash_q": _preset_glm47_flash_q,
    "glm_tiny_q": _preset_glm_tiny_q,
    "trinity_mini_q": _preset_trinity_mini_q,
    "trinity_tiny_q": _preset_trinity_tiny_q,
    "smallthinker_21b_q": _preset_smallthinker_21b_q,
    "smallthinker_tiny_q": _preset_smallthinker_tiny_q,
    "ouro_2p6b_q": _preset_ouro_2p6b_q,
    "ouro_tiny_q": _preset_ouro_tiny_q,
    "kimi_linear_48b_q": _preset_kimi_linear_48b_q,
    "kimi_linear_tiny_q": _preset_kimi_linear_tiny_q,
    "lfm2_24b_q": _preset_lfm2_24b_q,
    "lfm2_tiny_q": _preset_lfm2_tiny_q,
    "minicpm_sala_9b_q": _preset_minicpm_sala_9b_q,
    "minicpm_sala_tiny_q": _preset_minicpm_sala_tiny_q,
    "jamba2_3b_q": _preset_jamba2_3b_q,
    "jamba2_tiny_q": _preset_jamba2_tiny_q,
}


def get_config(name: str, **overrides: Any) -> RunConfig:
    if name not in PRESETS:
        raise KeyError(f"unknown config {name!r}; known: {sorted(PRESETS)}")
    cfg = PRESETS[name]()
    if overrides:
        cfg = cfg.replace(**overrides)
    return cfg
