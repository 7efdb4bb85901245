"""Network factory + model helpers. The decoder_q family's eight nets
(`DECODER_NETS`) share models/expert_layer.py (five of them),
models/windowed_gqa.py (four), models/mla.py (glm_moe_q,
kimi_linear_q), models/short_conv.py (kimi_linear_q, lfm2_moe_q),
models/q_head.py's column read (afmoe_q, smallthinker_q, and over a
tied head lfm2_moe_q) and, the fifth alone so far,
ops/chunked_delta_rule.py; the seventh (minicpm_sala_q) shares
ouro_q's held arithmetic and brings ops/lightning_attention.py and
ops/block_select_attention.py; the eighth (jamba_q) shares that held
arithmetic, short_conv.py's filter and block_select_attention's pool,
and brings ops/selective_scan.py. Importing this module runs no JAX."""

from ape_x_dqn_tpu.models.base import (
    hard_update, init_params, param_count, preprocess_obs, soft_update)
from ape_x_dqn_tpu.models.qnets import MLPQNet, NatureDQN, DuelingHead
from ape_x_dqn_tpu.models.lstm_q import ApeXLSTMQNet, LSTMState
from ape_x_dqn_tpu.models.dpg import DPGActor, DPGCritic
from ape_x_dqn_tpu.models.glm_moe_q import GlmMoeQNet
from ape_x_dqn_tpu.models.afmoe_q import AfmoeQNet
from ape_x_dqn_tpu.models.smallthinker_q import SmallThinkerQNet
from ape_x_dqn_tpu.models.ouro_q import OuroQNet
from ape_x_dqn_tpu.models.kimi_linear_q import KimiLinearQNet
from ape_x_dqn_tpu.models.lfm2_moe_q import Lfm2MoeQNet
from ape_x_dqn_tpu.models.minicpm_sala_q import MiniCpmSalaQNet
from ape_x_dqn_tpu.models.jamba_q import JambaQNet

# The decoder_q family's nets, the ONE place a decoder registers:
# network.kind -> (its block's name on configs.NetworkConfig, its
# class). The token-level Q-networks over GLM-4.7-Flash, Trinity-Mini,
# SmallThinker, Ouro, Kimi-Linear, LFM2, MiniCPM-SALA, Jamba2. A
# further decoder is its module here in models/, a row here, and its
# block, field and presets in configs.py: `DECODER_NETS`,
# `decoder_block`, runtime/family.family_of and tools/apexlint's
# config_coverage read this table or NetworkConfig's fields.
DECODERS = {
    "glm_moe_q": ("glm", GlmMoeQNet),
    "afmoe_q": ("afmoe", AfmoeQNet),
    "smallthinker_q": ("smallthinker", SmallThinkerQNet),
    "ouro_q": ("ouro", OuroQNet),
    "kimi_linear_q": ("kimi_linear", KimiLinearQNet),
    "lfm2_moe_q": ("lfm2_moe", Lfm2MoeQNet),
    "minicpm_sala_q": ("minicpm_sala", MiniCpmSalaQNet),
    "jamba_q": ("jamba", JambaQNet),
}
DECODER_NETS = {kind: net for kind, (_, net) in DECODERS.items()}


def decoder_block(net_cfg):
    """-> (the name of net_cfg's decoder block in NetworkConfig, the
    block)."""
    name = DECODERS[net_cfg.kind][0]
    return name, getattr(net_cfg, name)


def build_network(net_cfg, spec):
    """Build the module matching a NetworkConfig for an EnvSpec.

    For kind='dpg' returns (actor, critic); otherwise a single Q-network.
    """
    if net_cfg.kind == "mlp":
        return MLPQNet(num_actions=spec.num_actions,
                       hidden=tuple(net_cfg.mlp_hidden),
                       dueling=net_cfg.dueling,
                       compute_dtype=net_cfg.compute_dtype)
    if net_cfg.kind == "nature_cnn":
        return NatureDQN(num_actions=spec.num_actions,
                         channels=tuple(net_cfg.cnn_channels),
                         kernels=tuple(net_cfg.cnn_kernels),
                         strides=tuple(net_cfg.cnn_strides),
                         dense=net_cfg.torso_dense,
                         dueling=net_cfg.dueling,
                         compute_dtype=net_cfg.compute_dtype)
    if net_cfg.kind == "lstm_q":
        return ApeXLSTMQNet(num_actions=spec.num_actions,
                            lstm_size=net_cfg.lstm_size,
                            dense=net_cfg.torso_dense,
                            dueling=net_cfg.dueling,
                            compute_dtype=net_cfg.compute_dtype,
                            mlp_torso=len(spec.obs_shape) == 1)
    if net_cfg.kind in DECODER_NETS:
        from ape_x_dqn_tpu.parallel.mesh import has_expert_exchange

        return DECODER_NETS[net_cfg.kind](
            decoder_block(net_cfg)[1], compute_dtype=net_cfg.compute_dtype,
            expert_exchange=has_expert_exchange())
    if net_cfg.kind == "dpg":
        actor = DPGActor(action_dim=spec.action_dim,
                         action_low=spec.action_low,
                         action_high=spec.action_high,
                         hidden=tuple(net_cfg.dpg_hidden),
                         compute_dtype=net_cfg.compute_dtype)
        critic = DPGCritic(hidden=tuple(net_cfg.dpg_hidden),
                           compute_dtype=net_cfg.compute_dtype)
        return actor, critic
    raise ValueError(f"unknown network kind {net_cfg.kind!r}")
