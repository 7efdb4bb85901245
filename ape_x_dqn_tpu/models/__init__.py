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

# network.kind -> the net's class: the eight token-level Q-networks of the
# decoder_q family (GLM-4.7-Flash, Trinity-Mini, SmallThinker, Ouro,
# Kimi-Linear, LFM2, MiniCPM-SALA, Jamba2). A further decoder is a row here and in `decoder_block`, a
# config block, and a row in runtime/family.family_of
DECODER_NETS = {"glm_moe_q": GlmMoeQNet, "afmoe_q": AfmoeQNet,
                "smallthinker_q": SmallThinkerQNet, "ouro_q": OuroQNet,
                "kimi_linear_q": KimiLinearQNet,
                "lfm2_moe_q": Lfm2MoeQNet,
                "minicpm_sala_q": MiniCpmSalaQNet,
                "jamba_q": JambaQNet}


def decoder_block(net_cfg):
    """-> (the name of net_cfg's decoder block in NetworkConfig, the
    block)."""
    return {"glm_moe_q": ("glm", net_cfg.glm),
            "afmoe_q": ("afmoe", net_cfg.afmoe),
            "smallthinker_q": ("smallthinker", net_cfg.smallthinker),
            "ouro_q": ("ouro", net_cfg.ouro),
            "kimi_linear_q": ("kimi_linear", net_cfg.kimi_linear),
            "lfm2_moe_q": ("lfm2_moe", net_cfg.lfm2_moe),
            "minicpm_sala_q": ("minicpm_sala", net_cfg.minicpm_sala),
            "jamba_q": ("jamba", net_cfg.jamba),
            }[net_cfg.kind]


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
