"""models.DECODERS, the one place a decoder net registers, through
everything that reads it; and the routed blocks' share validation,
written once (configs._check_share)."""

import dataclasses

import pytest

from ape_x_dqn_tpu.configs import NetworkConfig, RunConfig
from ape_x_dqn_tpu.models import (
    DECODER_NETS, DECODERS, build_network, decoder_block)
from ape_x_dqn_tpu.runtime.family import family_of


@pytest.mark.parametrize("kind", sorted(DECODERS))
def test_a_row_is_all_a_decoder_registers(kind):
    name, net_class = DECODERS[kind]
    net_cfg = NetworkConfig(kind=kind)
    assert name in {f.name for f in dataclasses.fields(NetworkConfig)}
    assert dataclasses.is_dataclass(getattr(net_cfg, name))
    assert decoder_block(net_cfg) == (name, getattr(net_cfg, name))
    assert DECODER_NETS[kind] is net_class
    assert type(build_network(net_cfg, None)) is net_class
    assert family_of(RunConfig(network=net_cfg)) == "decoder_q"


def test_the_families_of_the_other_kinds():
    families = {kind: family_of(RunConfig(network=NetworkConfig(kind=kind)))
                for kind in ("mlp", "nature_cnn", "lstm_q", "dpg")}
    assert families == {"mlp": "dqn", "nature_cnn": "dqn",
                        "lstm_q": "r2d2", "dpg": "dpg"}


ROUTED = {"glm": "n_routed_experts", "afmoe": "num_experts",
          "smallthinker": "moe_num_primary_experts",
          "kimi_linear": "num_experts", "lfm2_moe": "num_experts"}


def _block(name):
    return type(getattr(NetworkConfig(), name))


@pytest.mark.parametrize("name", sorted(ROUTED))
def test_a_share_outside_its_count_is_refused(name):
    with pytest.raises(ValueError, match=rf"network\.{name}\.shard_index "
                                         r"must be in \[0, 2\) \(got 2\)"):
        _block(name)(shard_count=2, shard_index=2)
    assert _block(name)(shard_count=2, shard_index=1).shard_index == 1


@pytest.mark.parametrize("name", sorted(ROUTED))
def test_a_count_that_does_not_divide_the_experts_is_refused(name):
    experts = getattr(_block(name)(), ROUTED[name])
    assert experts % 7
    with pytest.raises(ValueError, match=rf"network\.{name}\.shard_count=7 "
                                         rf"must divide {ROUTED[name]}="):
        _block(name)(shard_count=7)


@pytest.mark.parametrize("name", ["afmoe", "kimi_linear", "lfm2_moe"])
def test_the_vocabulary_goes_its_own_count_of_ways(name):
    block = _block(name)
    assert block().vocab_size % 16 == 0 and block().vocab_size % 7
    assert block(shard_count=16, vocab_shard_count=8).vocab_shard_count == 8
    with pytest.raises(ValueError, match="the vocabulary's 7 shares"):
        block(shard_count=2, vocab_shard_count=7)
