"""The decoder family's sixth net (network.kind "lfm2_moe_q") at tiny
widths on the CPU: hidden 32, both kinds of layer and both kinds of FFN
(conv + dense, attention + experts, conv + experts), 4 query heads to 2
key-value heads of 12 (48: no attention width is the hidden size), 8
experts top-2, a vocabulary of 64 whose embedding IS the head,
sequences of 32 with a burn-in of 12 and attention blocks of 4, so that
the prefix boundary falls inside a block. The net against
benchmarks/reference/lfm2_moe_q.py (Q, loss, priorities, every gradient
leaf, the tied matrix's the sum of its two uses), forced and unforced
selection; the burn-in through both kinds of state against one pass;
the five departures the reference can make are seen; `conv_positions`
reads layers x positions x batch; the family's rows build through
ApexDriver and the server answers a window of one token; the HBM budget
admits the chip's share and refuses the whole model. The conv operator
and the tied head's read as bare functions:
tests/test_short_conv_and_tied_head.py; the shares add up:
tests/test_kimi_linear_q.py's parametrised case."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.configs import get_config
from ape_x_dqn_tpu.envs import make_env
from ape_x_dqn_tpu.models import DECODER_NETS, build_network, decoder_block
from ape_x_dqn_tpu.models.lfm2_moe_q import Lfm2MoeQNet
from ape_x_dqn_tpu.runtime.family import (
    ACTOR_STATE, family_of, hbm_price, learner_family, reads_by_column,
    server_apply_fn, stored_state_spec)
from benchmarks.harness import lfm2_params as mapper
from benchmarks.reference import lfm2_moe_q as ref

L, BURN, B = 32, 12, 3
BLOCKS = (4, 4)
CONFIG_FILE = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "lfm2_24b_ep8_1chip.json")


def tiny(shards: int = 2, index: int = 0, dtype: str = "float32",
         balanced: bool = False, **fields):
    cfg = get_config("lfm2_tiny_q")
    lf = dataclasses.replace(
        cfg.network.lfm2_moe, shard_count=shards, shard_index=index,
        force_balanced_routing=balanced, **fields)
    return cfg.replace(
        network=dataclasses.replace(cfg.network, lfm2_moe=lf,
                                    compute_dtype=dtype),
        env=dataclasses.replace(cfg.env,
                                num_tokens=lf.vocab_size // shards))


def net_and_params(cfg, seed: int = 0):
    """The net with attention blocks of 4, and its seeded parameters
    WITH EVERY LAYER'S MATRICES AND FILTERS TIMES 8: at hidden 32 a
    matrix of normal(0, 0.02) makes a conv operator's output (three
    projections and a filter deep) a ten-thousandth of the stream, and
    a wrong operator would pass every comparison below; times 8 a
    projection's output is of order 1, as at hidden 2,048."""
    net = Lfm2MoeQNet(cfg.network.lfm2_moe, cfg.network.compute_dtype,
                      attn_blocks=BLOCKS)
    params = net.init(jax.random.PRNGKey(seed))
    params["layers"] = jax.tree.map(
        lambda x: 8.0 * x if x.ndim >= 2 else x, params["layers"])
    return net, params


def batch(cfg, seed: int = 1) -> dict:
    rng = np.random.default_rng(seed)
    v = cfg.env.num_tokens
    mask = np.ones((B, L), np.float32)
    mask[1, 25:] = 0.0                      # an episode's tail
    terminals = np.zeros((B, L), np.float32)
    terminals[1, 24] = 1.0
    terminals[2, 17] = 1.0                  # a terminal mid-sequence
    return {"obs": rng.integers(0, v, (B, L)).astype(np.int32),
            "actions": rng.integers(0, v, (B, L)).astype(np.int32),
            "rewards": (rng.integers(0, 4, (B, L)) == 0).astype(np.float32),
            "terminals": terminals, "mask": mask}


def system_loss(cfg, net):
    family = learner_family(cfg, net)
    return lambda p, tp, items, w: family.loss_fn(
        p, tp, family.make_batch(items), w)


def reference_loss(cfg, net, params, target, items, w, sizes=None, **kw):
    return ref.loss_and_gradients(
        mapper.reference_params(params), mapper.reference_params(target),
        items["obs"], items["actions"], items["rewards"],
        items["terminals"], items["mask"], w,
        sizes=sizes or mapper.sizes(cfg.network.lfm2_moe,
                                    net.router_trains),
        burn_in=cfg.replay.burn_in, n_step=cfg.learner.n_step,
        gamma=cfg.learner.gamma, eta=cfg.replay.priority_eta,
        huber_delta=cfg.learner.huber_delta, **kw)


def test_param_count_and_the_published_share():
    cfg = tiny()
    net, params = net_and_params(cfg)
    assert net.param_count() == sum(x.size for x in jax.tree.leaves(params))
    assert set(params) == {"embed_tokens", "layers", "embedding_norm"}
    conv, attn = params["layers"][0], params["layers"][1]
    assert {"in_proj", "conv_weight", "out_proj"} <= set(conv)
    assert conv["in_proj"].shape == (32, 96)
    assert conv["conv_weight"].shape == (3, 32) and "q_proj" not in conv
    assert attn["q_proj"].shape == (32, 48)
    assert attn["k_proj"].shape == (32, 24)
    assert attn["q_layernorm"].shape == (12,) and "in_proj" not in attn
    assert "experts" not in conv["mlp"]
    assert set(attn["mlp"]) == {"gate", "e_score_correction_bias",
                                "experts"}      # no shared expert
    from ape_x_dqn_tpu.runtime.train import apply_overrides

    with open(CONFIG_FILE) as fh:
        overrides = json.load(fh)["overrides"]
    share = apply_overrides(get_config("lfm2_24b_q"), overrides)
    big = build_network(share.network, None)
    # ISSUE 50's count, and 64 bias values a routed layer
    assert big.param_count() == 469_284_992 + 4 * 64
    assert (big.num_actions, big.experts_held, big.head_dim) == (8_192, 8, 64)
    assert share.network.lfm2_moe.layer_types == (
        "conv", "full_attention", "conv", "conv", "conv")
    assert big.num_conv_layers == 4 and not big.router_trains


@pytest.mark.parametrize("shards,index,balanced", [
    (1, 0, False), (2, 1, True)])
def test_loss_and_gradients_match_reference_float32(shards, index, balanced):
    """Q, loss, priorities, the selection and every gradient leaf - the
    conv operator against the tap sum on a padded array, the blockwise
    attention against a materialised softmax, the column read over the
    tied matrix against x E^T whole - under the model's own selection
    and the forced one; the router's gradient is zero in a share; the
    family's two counters of this net read what the shapes give. In the
    share's case also THE TIED MATRIX'S TWO USES TAKEN APART in the
    reference: the lookup alone (the head a constant), the head alone
    (the lookup a constant), both live, and the system's gradient
    their sum."""
    cfg = tiny(shards, index, balanced=balanced)
    net, params = net_and_params(cfg)
    _, target = net_and_params(cfg, seed=5)
    items, w = batch(cfg), jnp.asarray([1.0, 0.5, 0.7])
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        system_loss(cfg, net), has_aux=True))(params, target, items, w)
    (want, raux), rgrads = jax.jit(
        lambda p, t: reference_loss(cfg, net, p, t, items, w))(params, target)
    np.testing.assert_allclose(loss, want, atol=1e-5)
    np.testing.assert_allclose(aux["q"], raux["q"], atol=1e-5)
    np.testing.assert_allclose(aux["td_abs"], raux["priorities"], atol=1e-5)
    assert (np.sort(aux["topk_online"], -1)
            == np.sort(raux["topk_online"], -1)).all()
    rgrads = mapper.system_gradients(rgrads)
    assert (jax.tree.structure(grads) == jax.tree.structure(rgrads))
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), exp in zip(flat, jax.tree.leaves(rgrads)):
        np.testing.assert_allclose(got, exp, atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))
    assert bool(np.any(grads["layers"][1]["mlp"]["gate"])) == (shards == 1)
    for layer in grads["layers"]:       # every operator parameter is live
        for name, g in layer.items():
            if name != "mlp":
                assert np.any(g), name
    # 2 conv layers x 32 positions x 3 sequences; online + target columns
    assert float(aux["conv_positions"]) == 2 * L * B
    assert float(aux["head_columns"]) == 2 * B * (L - BURN)
    if shards == 1:
        return
    sizes = mapper.sizes(cfg.network.lfm2_moe, net.router_trains)
    rp = mapper.reference_params(params)
    trained = [items[k][:, BURN:] for k in (
        "actions", "rewards", "terminals", "mask")]

    def loss_of(lookup, head):
        x = ref.embed({"embed": lookup}, items["obs"])
        for i, p in enumerate(rp["layers"]):
            x, _, _ = ref.block(p, x, sizes, BURN, tokens=items["obs"],
                                layer=i)
        q = ref.head({"final_norm": rp["final_norm"], "head": head},
                     x, sizes)[:, BURN:]
        return ref.td_loss(
            q, q_target, *trained, w,
            n_step=cfg.learner.n_step, gamma=cfg.learner.gamma,
            eta=cfg.replay.priority_eta)[0]

    q_target = jax.jit(lambda t: ref.forward(
        mapper.reference_params(t), items["obs"], sizes, BURN)[0])(
        target)[:, BURN:]
    lookup, head = jax.jit(jax.grad(loss_of, argnums=(0, 1)))(
        rp["embed"], rp["embed"])
    assert np.abs(lookup).max() > 1e-5 and np.abs(head).max() > 1e-5
    np.testing.assert_allclose(grads["embed_tokens"], lookup + head,
                               atol=1e-5)


def test_prefix_then_segment_through_the_state_equals_one_pass():
    """The trained steps from the two kinds of state the burn-in leaves
    equal the REFERENCE's one pass over the whole sequence (and the
    system's own): a conv layer's state is two rows after 12 positions
    and after 32, an attention layer's has keys and values per
    position."""
    cfg = tiny()
    net, params = net_and_params(cfg)
    tokens = batch(cfg)["obs"]
    apply = jax.jit(net.apply)
    whole, whole_state = apply(params, tokens, ())
    _, state = apply(params, tokens[:, :BURN], ())
    segment, after = apply(params, tokens[:, BURN:], state)
    np.testing.assert_allclose(segment, whole[:, BURN:], atol=1e-5)
    want, _, _ = jax.jit(lambda p: ref.forward(
        mapper.reference_params(p), tokens,
        mapper.sizes(cfg.network.lfm2_moe)))(params)
    np.testing.assert_allclose(segment, want[:, BURN:], atol=1e-5)
    for kind, first, second, one_pass in zip(
            cfg.network.lfm2_moe.layer_types, state, after, whole_state):
        if kind == "conv":
            (tail, seen), (tail2, seen2) = first, second
            assert tail.shape == tail2.shape == (B, 2, 32)
        else:
            (k, v, seen), (k2, v2, seen2) = first, second
            assert k.shape == v.shape == (B, BURN, 2, 12)
            assert k2.shape == v2.shape == (B, L, 2, 12)
        assert (int(seen), int(seen2)) == (BURN, L)
        for a, b in zip(jax.tree.leaves(second), jax.tree.leaves(one_pass)):
            np.testing.assert_allclose(a, b, atol=1e-5)
    # the state is POST-GATE, PRE-FILTER: the last two rows of B * x~
    u = ref.rms_norm(params["embed_tokens"][tokens[:, :BURN]],
                     params["layers"][0]["operator_norm"], 1e-5)
    bcx = u @ params["layers"][0]["in_proj"]
    np.testing.assert_allclose(
        state[0][0], (bcx[..., :32] * bcx[..., 64:])[:, -2:], atol=1e-6)


def test_a_window_of_one_token_and_a_prefix_shorter_than_the_filter():
    """The server's stateless window at an episode's start holds one
    token: the filter pads with zeros on the left. One token, then one
    more, then the rest through the state equal all at once; and
    `apply_window` answers the first query with Q at that token."""
    cfg = tiny()
    net, params = net_and_params(cfg)
    tokens = batch(cfg)["obs"][:, :5]
    apply = jax.jit(net.apply)
    whole, _ = apply(params, tokens, ())
    q1, state = apply(params, tokens[:, :1], ())
    np.testing.assert_array_equal(state[0][0][:, 0], 0.0)   # padded left
    q2, state = apply(params, tokens[:, 1:2], state)
    q3, _ = apply(params, tokens[:, 2:], state)
    np.testing.assert_allclose(jnp.concatenate([q1, q2, q3], axis=1), whole,
                               atol=1e-5)
    window = server_apply_fn("decoder_q", net)
    out = jax.jit(window)(params, {
        "obs": tokens[:, 0], "ctx": jnp.zeros((B, L), jnp.int32),
        "n": jnp.zeros((B,), jnp.int32)})
    np.testing.assert_allclose(out["q"], whole[:, 0], atol=1e-5)
    assert out["n"].tolist() == [1] * B


DEPARTURES = ("conv_tail_ignored", "conv_out_gate_left_out",
              "conv_silu_added", "qk_norm_left_out", "head_untied")


@pytest.fixture(scope="module")
def trained_q():
    """The system's Q on the trained steps, through the state the
    prefix leaves; the model's sizes; the reference at given sizes."""
    cfg = tiny(shards=1)
    net, params = net_and_params(cfg)
    tokens = batch(cfg)["obs"]
    apply = jax.jit(net.apply)
    _, state = apply(params, tokens[:, :BURN], ())
    got, _ = apply(params, tokens[:, BURN:], state)
    forward = jax.jit(lambda sz: ref.forward(
        mapper.reference_params(params), tokens, sz, BURN)[0][:, BURN:],
        static_argnums=0)
    return np.asarray(got), mapper.sizes(cfg.network.lfm2_moe), forward


@pytest.mark.parametrize("departure", DEPARTURES)
def test_the_reference_tells_each_departure_apart(trained_q, departure):
    """What the benchmark's check must refuse: against the reference
    with one departure made, the system's Q on the trained steps is far
    outside the 1e-5 the model's own equations are held to -
    `conv_tail_ignored` at the two positions behind the prefix."""
    got, sizes, forward = trained_q
    apart = np.abs(np.asarray(forward(sizes._replace(**{departure: True})))
                   - got)
    assert float(apart.max()) > 1e-2
    if departure == "conv_tail_ignored":
        assert float(apart[:, :2].max()) == float(apart.max())


def test_conv_positions_is_among_the_keys_of_a_net_with_conv_layers():
    """The family's counter: positions that passed a conv operator,
    summed where the operator runs, among the metric keys of a net with
    conv layers beside `head_columns` (this net reads its tied head by
    column) and none of a scan layer's; what a train step reads is in
    the reference test above and in the ApexDriver test below."""
    cfg = tiny()
    net, params = net_and_params(cfg)
    assert reads_by_column(net)
    family = learner_family(cfg, net)
    assert {"conv_positions", "head_columns", "moe_rows"} <= set(
        family.metric_keys)
    assert "kda_chunks" not in family.metric_keys
    _, _, stats = jax.jit(net.apply_with_stats)(
        params, batch(cfg)["obs"][:, :5], ())
    assert int(stats["conv_positions"]) == 2 * 5 * B


def test_family_rows():
    cfg = tiny()
    assert family_of(cfg) == "decoder_q"
    assert ACTOR_STATE["decoder_q"].stored == ()
    assert stored_state_spec("decoder_q", cfg) == {}
    net = build_network(cfg.network, make_env(cfg.env).spec)
    assert type(net) is Lfm2MoeQNet is DECODER_NETS["lfm2_moe_q"]
    assert net.num_actions == 32
    name, block = decoder_block(cfg.network)
    assert name == "lfm2_moe" and block is cfg.network.lfm2_moe
    lf = cfg.network.lfm2_moe
    for field in ({"conv_bias": True}, {"use_expert_bias": False},
                  {"tie_embedding": False}):
        with pytest.raises(NotImplementedError, match="only the published"):
            Lfm2MoeQNet(dataclasses.replace(lf, **field))
    with pytest.raises(ValueError, match="layer_types"):
        Lfm2MoeQNet(dataclasses.replace(lf, num_hidden_layers=2))
    with pytest.raises(ValueError, match="max_position_embeddings"):
        net.apply(net.init(jax.random.key(0)), jnp.zeros((1, 33), jnp.int32))
    # head_dim 0 is hidden over the heads, as the model's file has it
    assert Lfm2MoeQNet(dataclasses.replace(lf, head_dim=0)).head_dim == 8
    # the vocabulary's rows may go fewer ways than the experts
    wide = dataclasses.replace(lf, shard_count=8, vocab_shard_count=2)
    assert (Lfm2MoeQNet(wide).num_actions,
            Lfm2MoeQNet(wide).experts_held) == (32, 1)


def test_apexdriver_builds_and_trains():
    from ape_x_dqn_tpu.runtime.driver import ApexDriver
    from ape_x_dqn_tpu.runtime.learner import SingleChipLearner

    cfg = tiny()
    cfg = cfg.replace(actors=dataclasses.replace(cfg.actors, num_actors=0),
                      eval_episodes=0, eval_every_steps=0)
    driver = ApexDriver(cfg)
    try:
        assert type(driver.learner) is SingleChipLearner
        assert driver.learner.family.name == "decoder_q"
        assert type(driver.net) is Lfm2MoeQNet
        state = driver.state
        rng = np.random.default_rng(0)
        n = 16
        items = {"obs": rng.integers(0, 32, (n, L)).astype(np.int32),
                 "actions": rng.integers(0, 32, (n, L)).astype(np.int32),
                 "rewards": rng.normal(size=(n, L)).astype(np.float32),
                 "terminals": np.zeros((n, L), np.float32),
                 "mask": np.ones((n, L), np.float32)}
        state = driver.learner.add(state, items, jnp.ones(n))
        before = jax.device_get(state.params)
        state, m = driver.learner.train_many(state, 2)
        assert int(state.step) == 2 and np.isfinite(float(m["loss"]))
        for key in ("valid_frac", "moe_rows", "moe_rows_grad",
                    "moe_load_max_over_mean", "moe_compact_share",
                    "conv_positions", "head_columns"):
            assert np.isfinite(float(m[key])), key
        assert float(m["conv_positions"]) == 2 * L * cfg.learner.batch_size
        after = jax.device_get(state.params)
        assert not np.array_equal(before["embed_tokens"],
                                  after["embed_tokens"])
        for name in ("in_proj", "conv_weight", "out_proj"):
            assert not np.array_equal(before["layers"][2][name],
                                      after["layers"][2][name]), name
        for name in ("q_proj", "k_layernorm", "out_proj"):
            assert not np.array_equal(before["layers"][1][name],
                                      after["layers"][1][name]), name
        # a share without the exchange: the router is held fixed
        np.testing.assert_array_equal(before["layers"][1]["mlp"]["gate"],
                                      after["layers"][1]["mlp"]["gate"])
    finally:
        driver.server.stop()


def test_hbm_budget_admits_the_share_and_refuses_the_whole_model():
    from ape_x_dqn_tpu.runtime.train import apply_overrides
    from ape_x_dqn_tpu.utils import hbm

    v5e = int(15.75 * 1024 ** 3)

    def check(cfg):
        net = build_network(cfg.network, None)
        return hbm.check_hbm_fits(
            cfg, (), np.int32, param_count=net.param_count(),
            hbm_bytes=v5e, **hbm_price(cfg, net))

    whole = get_config("lfm2_24b_q")
    assert build_network(whole.network, None).param_count() > 23e9
    with pytest.raises(ValueError, match="GiB per device"):
        check(whole)
    with open(CONFIG_FILE) as fh:
        overrides = json.load(fh)["overrides"]
    share = apply_overrides(whole, overrides)
    check(share)
    # a conv layer's two rows do not grow with the prefix, the one
    # attention layer's keys and values do: what the price adds for the
    # burn-in, in bfloat16
    net = build_network(share.network, None)
    short, long = (net.sequence_state_bytes(1, n) for n in (128, 4096))
    assert long - short == (4096 - 128) * 2 * 8 * 64 * 2
    assert short - 128 * 2 * 8 * 64 * 2 == 4 * 2 * 2048 * 2     # 8 KiB each
