"""The decoder family's third net (network.kind "smallthinker_q") at tiny
widths on the CPU: hidden 64, 7 query heads to each of 2 key-value heads
of 16, 8 experts top-3, a vocabulary of 64, one period of four layers
(global, sliding x 3), A WINDOW OF 8 inside sequences of 32 with a
burn-in of 12 and attention blocks of 4, so that the window, the
trimmed cache, the prefix boundary and the block boundaries all bite.
The net against benchmarks/reference/smallthinker_q.py (Q, loss,
priorities, every gradient leaf), forced and unforced selection; the
burn-in through the cache against one pass; the eight shares add up;
the three departures the reference can make are seen; a block's
recomputation keeps the selection; the router trains whole and not in a
share; the family's rows build through ApexDriver; a run with actors
completes; the HBM budget admits the chip's share and refuses the
whole model."""

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
from ape_x_dqn_tpu.models.expert_layer import (
    SELECTION, SOFTMAX_SELECTED, expert_ffn, plan)
from ape_x_dqn_tpu.models.smallthinker_q import SmallThinkerQNet
from ape_x_dqn_tpu.runtime.family import (
    ACTOR_STATE, family_of, learner_family, stored_state_spec)
from benchmarks.harness import smallthinker_params as mapper
from benchmarks.reference import smallthinker_q as ref

L, BURN, B, WINDOW = 32, 12, 3, 8
BLOCKS = (4, 4)
CONFIG_FILE = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "smallthinker_21b_ep8_1chip.json")


def tiny(shards: int = 2, index: int = 0, dtype: str = "float32",
         balanced: bool = False, **fields):
    cfg = get_config("smallthinker_tiny_q")
    st = dataclasses.replace(
        cfg.network.smallthinker, shard_count=shards, shard_index=index,
        force_balanced_routing=balanced, **fields)
    return cfg.replace(
        network=dataclasses.replace(cfg.network, smallthinker=st,
                                    compute_dtype=dtype),
        env=dataclasses.replace(cfg.env,
                                num_tokens=st.vocab_size // shards))


def net_and_params(cfg, seed: int = 0):
    """The net with blocks of 4: a 20-token segment crosses four."""
    net = SmallThinkerQNet(cfg.network.smallthinker,
                           cfg.network.compute_dtype, attn_blocks=BLOCKS)
    return net, net.init(jax.random.PRNGKey(seed))


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
        sizes=sizes or mapper.sizes(cfg.network.smallthinker,
                                    net.router_trains),
        burn_in=cfg.replay.burn_in, n_step=cfg.learner.n_step,
        gamma=cfg.learner.gamma, eta=cfg.replay.priority_eta,
        huber_delta=cfg.learner.huber_delta, **kw)


def test_param_count_and_the_published_share():
    cfg = tiny()
    net, params = net_and_params(cfg)
    assert net.param_count() == sum(x.size for x in jax.tree.leaves(params))
    assert set(params["layers"][0]) == {
        "q_proj", "k_proj", "v_proj", "o_proj", "input_layernorm",
        "post_attention_layernorm", "mlp"}
    # routed only: no shared expert, no selection bias
    assert set(params["layers"][0]["mlp"]) == {"gate", "experts"}
    assert net.s.num_attention_heads // net.s.num_key_value_heads == 7
    from ape_x_dqn_tpu.runtime.train import apply_overrides

    with open(CONFIG_FILE) as fh:
        overrides = json.load(fh)["overrides"]
    share = apply_overrides(get_config("smallthinker_21b_q"), overrides)
    big = build_network(share.network, None)
    assert big.param_count() == 370_547_200
    assert (big.num_actions, big.experts_held) == (18_992, 8)
    assert not big.router_trains


@pytest.mark.parametrize("shards,index,balanced", [
    (1, 0, False), (2, 0, False), (2, 1, False), (8, 3, False),
    (1, 0, True), (2, 0, True)])
def test_loss_and_gradients_match_reference_float32(shards, index, balanced):
    """Q, loss, priorities, the selection and every gradient leaf, with
    a window shorter than the sequence and a prefix longer than it,
    under the model's own selection and the forced one; the router's
    gradient is zero in a share and not at shard_count = 1."""
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
    for got, exp in zip(jax.tree.leaves(grads), jax.tree.leaves(rgrads)):
        np.testing.assert_allclose(got, exp, atol=1e-5)
    for layer in grads["layers"]:
        assert bool(np.any(layer["mlp"]["gate"])) == (shards == 1)


def test_prefix_then_segment_through_the_cache_equals_one_pass():
    """The trained steps through the two kinds of cache the burn-in
    leaves equal the REFERENCE's one causal pass over the whole
    sequence (and the system's own); the global layer keeps every
    prefix position, a sliding one its last window - 1."""
    cfg = tiny()
    net, params = net_and_params(cfg)
    tokens = batch(cfg)["obs"]
    apply = jax.jit(net.apply)
    whole, _ = apply(params, tokens, ())
    _, state = apply(params, tokens[:, :BURN], ())
    segment, after = apply(params, tokens[:, BURN:], state)
    np.testing.assert_allclose(segment, whole[:, BURN:], atol=1e-5)
    want, _, _ = jax.jit(lambda p: ref.forward(
        mapper.reference_params(p), tokens,
        mapper.sizes(cfg.network.smallthinker)))(params)
    np.testing.assert_allclose(segment, want[:, BURN:], atol=1e-5)
    layout = cfg.network.smallthinker.sliding_window_layout
    assert layout == (0, 1, 1, 1)
    for sliding, (k, v, seen), (k2, _, seen2) in zip(layout, state, after):
        assert int(seen) == BURN and int(seen2) == L
        assert k.shape == v.shape == (
            B, WINDOW - 1 if sliding else BURN, 2, 16)
        assert k2.shape[1] == (WINDOW - 1 if sliding else L)


def test_global_layers_are_position_free_and_sliding_ones_are_not():
    """One cached key, then one token: where the token stands changes
    its Q-values through RoPE against the cached key on `rope_layout`
    1 layers and nothing on layout 0 ones."""
    for layout, moved in (((0,) * 4, False), ((1,) * 4, True)):
        cfg = tiny(rope_layout=layout, sliding_window_layout=layout)
        net, params = net_and_params(cfg)
        kv = jax.random.normal(jax.random.PRNGKey(7), (2, 1, 1, 2, 16))
        apply = jax.jit(net.apply)

        def at(seen):
            state = tuple((kv[0], kv[1], jnp.int32(seen)) for _ in layout)
            return apply(params, jnp.asarray([[5]]), state)[0]

        assert (not np.allclose(at(1), at(5), atol=1e-6)) == moved, layout


def test_the_eight_shares_add_up():
    """The routed parts that eight shares of one expert each compute,
    from ONE plan read off another tensor than the rows, add up to what
    the uncut reference gives for the whole layer."""
    whole = tiny(shards=1)
    _, params = net_and_params(whole)
    layer = params["layers"][1]
    z = jax.random.normal(jax.random.PRNGKey(3), (B, L, 64))
    read = jax.random.normal(jax.random.PRNGKey(4), (B, L, 64))
    sizes = mapper.sizes(whole.network.smallthinker)
    want, own, _ = ref.expert_layer(
        mapper.reference_layer(params, 1), z, read, sizes, None,
        lambda a: a)
    total, rows = jnp.zeros_like(want), 0
    for index in range(8):
        net, _ = net_and_params(tiny(shards=8, index=index))
        mlp = {"gate": layer["mlp"]["gate"],
               "experts": {k: v[index:index + 1]
                           for k, v in layer["mlp"]["experts"].items()}}
        planned = plan(mlp, read.reshape(B * L, -1), net.share,
                       scoring=SOFTMAX_SELECTED)
        out, n, ids = expert_ffn(mlp, z, jnp.float32, net.share,
                                 planned=planned, act=jax.nn.relu)
        assert (np.sort(ids, -1) == np.sort(own, -1)).all()
        total = total + out
        rows += int(n.sum())
    np.testing.assert_allclose(total, want, atol=1e-5)
    assert rows == B * L * sizes.top_k


@pytest.mark.parametrize("departure", [
    {"activation": "silu"}, {"router_reads": "expert_input"},
    {"weights": "sigmoid_normalised"}])
def test_the_reference_tells_each_departure_apart(departure):
    """What the benchmark's check must refuse: against the reference
    with SiLU for ReLU, the router fed from N2(h), or sigmoid-normalised
    weights (forced to the system's selection, so only the arithmetic
    differs) the system's Q-values are far outside rounding."""
    cfg = tiny(shards=1, balanced=True)
    net, params = net_and_params(cfg)
    # logits of order 1, as at the published hidden size (0.02 x
    # sqrt(2560)): at hidden 64 every scoring is nearly uniform
    for layer in params["layers"]:
        layer["mlp"]["gate"] = layer["mlp"]["gate"] * 6.0
    items, w = batch(cfg), jnp.ones(B)
    _, aux = jax.jit(system_loss(cfg, net))(params, params, items, w)
    sizes = mapper.sizes(cfg.network.smallthinker, net.router_trains)

    def q_of(sz):
        (_, raux), _ = jax.jit(lambda p: reference_loss(
            cfg, net, p, p, items, w, sizes=sz,
            forced_online=aux["topk_online"],
            forced_target=aux["topk_target"]))(params)
        return np.asarray(raux["q"])

    np.testing.assert_allclose(aux["q"], q_of(sizes), atol=1e-5)
    off = np.abs(np.asarray(aux["q"]) - q_of(sizes._replace(**departure)))
    assert np.quantile(off, 0.95) > 100 * 1e-5


def test_a_blocks_recomputation_keeps_the_selection():
    """The backward pass's recomputation of a block reads the ids the
    forward pass chose and does not decide the selection again
    (expert_layer.SELECTION is the one value the checkpoint keeps): the
    saved residuals of one application name it once for each layer, as
    int32 - a near-tie cannot fall the other way in an integer."""
    from jax._src.ad_checkpoint import saved_residuals

    cfg = tiny()
    net, params = net_and_params(cfg)
    tokens = jnp.asarray(batch(cfg)["obs"])
    saved = saved_residuals(
        lambda p: net.apply(p, tokens, ())[0].sum(), params)
    kept = [a for a, why in saved if SELECTION in why]
    assert len(kept) == cfg.network.smallthinker.num_hidden_layers
    assert all(str(a.dtype) == "int32" for a in kept)


def test_the_third_net_hands_the_expert_layer_its_own_numbers():
    cfg = get_config("smallthinker_tiny_q")
    net = build_network(cfg.network, None)
    assert type(net) is DECODER_NETS["smallthinker_q"]
    name, block = decoder_block(cfg.network)
    assert name == "smallthinker" and block is cfg.network.smallthinker
    assert net.share.top_k == 3 and net.share.scale == 1.0


def test_family_rows():
    cfg = tiny()
    assert family_of(cfg) == "decoder_q"
    assert ACTOR_STATE["decoder_q"].stored == ()
    assert stored_state_spec("decoder_q", cfg) == {}
    net = build_network(cfg.network, make_env(cfg.env).spec)
    assert type(net) is SmallThinkerQNet and net.num_actions == 32
    with pytest.raises(ValueError, match="one entry for each layer"):
        SmallThinkerQNet(dataclasses.replace(cfg.network.smallthinker,
                                             num_hidden_layers=2))
    with pytest.raises(NotImplementedError, match="published scoring"):
        SmallThinkerQNet(dataclasses.replace(cfg.network.smallthinker,
                                             norm_topk_prob=False))
    with pytest.raises(ValueError, match="max_position_embeddings"):
        net.apply(net.init(jax.random.PRNGKey(0)),
                  jnp.zeros((1, 33), jnp.int32), ())


def test_env_and_family_must_agree_on_the_vocabulary():
    from ape_x_dqn_tpu.runtime.driver import ApexDriver

    cfg = tiny()
    cfg = cfg.replace(env=dataclasses.replace(cfg.env, num_tokens=64))
    with pytest.raises(ValueError, match="network.smallthinker.vocab_size"):
        ApexDriver(cfg)


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
        assert type(driver.net) is SmallThinkerQNet
        state = driver.state
        rng = np.random.default_rng(0)
        n = 16
        items = {"obs": rng.integers(0, 32, (n, L)).astype(np.int32),
                 "actions": rng.integers(0, 32, (n, L)).astype(np.int32),
                 "rewards": rng.normal(size=(n, L)).astype(np.float32),
                 "terminals": np.zeros((n, L), np.float32),
                 "mask": np.ones((n, L), np.float32)}
        state = driver.learner.add(state, items, jnp.ones(n))
        before = jax.device_get(state.params["layers"][1])
        state, m = driver.learner.train_many(state, 2)
        assert int(state.step) == 2 and np.isfinite(float(m["loss"]))
        for key in ("valid_frac", "moe_rows", "moe_rows_grad",
                    "moe_load_max_over_mean", "moe_compact_share"):
            assert np.isfinite(float(m[key])), key
        after = jax.device_get(state.params["layers"][1])
        assert not np.array_equal(before["q_proj"], after["q_proj"])
        assert not np.array_equal(before["mlp"]["experts"]["up_proj"],
                                  after["mlp"]["experts"]["up_proj"])
        # a share without the exchange: the router is held fixed
        np.testing.assert_array_equal(before["mlp"]["gate"],
                                      after["mlp"]["gate"])
    finally:
        driver.server.stop()


def test_train_run_with_actors_completes(tmp_path):
    from ape_x_dqn_tpu.runtime import train

    out = tmp_path / "m.jsonl"
    argv = ["--config", "smallthinker_tiny_q", "--actors", "2",
            "--max-grad-steps", "8", "--wall-clock-limit", "120",
            "--metrics-file", str(out), "--set", "eval_episodes=1",
            "--set", "eval_max_frames=100", "--set", "eval_every_steps=0"]
    assert train.main(argv) == 0
    assert os.path.getsize(out) > 0


def test_hbm_budget_admits_the_share_and_refuses_the_whole_model():
    from ape_x_dqn_tpu.runtime.family import hbm_price
    from ape_x_dqn_tpu.runtime.train import apply_overrides
    from ape_x_dqn_tpu.utils import hbm

    v5e = int(15.75 * 1024 ** 3)

    def check(cfg):
        net = build_network(cfg.network, None)
        return hbm.check_hbm_fits(
            cfg, (), np.int32, param_count=net.param_count(),
            hbm_bytes=v5e, **hbm_price(cfg, net))

    whole = get_config("smallthinker_21b_q")
    assert build_network(whole.network, None).param_count() > 21e9
    with pytest.raises(ValueError, match="GiB per device"):
        check(whole)
    with open(CONFIG_FILE) as fh:
        overrides = json.load(fh)["overrides"]
    check(apply_overrides(whole, overrides))
