"""The decoder family's fourth net (network.kind "ouro_q") at tiny
widths on the CPU: hidden 64, 4 ungrouped heads of 16, an MLP of 96, a
vocabulary of 64, TWO LAYERS RUN FOUR TIMES WITH THE SAME WEIGHTS,
sequences of 32 with a burn-in of 12 and attention blocks of 4, so that
the prefix boundary and the block boundaries bite. The net against
benchmarks/reference/ouro_q.py (Q, loss, priorities, every gradient
leaf); THE TIE TO THE MODEL: a reference with four separate copies of
the stack, one per loop step, whose forward equals the looped net's and
whose per-copy gradients add up to its gradient leaf by leaf; the
burn-in through the cache per (loop step, layer) against one pass; the
four departures the reference can make are seen; the loop is one scan in
the lowered program; the exit gate feeds a counter and gets no
gradient; the family's rows build through ApexDriver; a run with actors
completes; the HBM budget admits the chip's cut and refuses the whole
model."""

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
from ape_x_dqn_tpu.models.ouro_q import OuroQNet
from ape_x_dqn_tpu.runtime.family import (
    ACTOR_STATE, family_of, learner_family, stored_state_spec)
from benchmarks.harness import ouro_params as mapper
from benchmarks.reference import ouro_q as ref

L, BURN, B = 32, 12, 3
STEPS, LAYERS = 4, 2
BLOCKS = (4, 4)
CONFIG_FILE = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "ouro_2p6b_1chip.json")


def tiny(dtype: str = "float32", **fields):
    cfg = get_config("ouro_tiny_q")
    ou = dataclasses.replace(cfg.network.ouro, **fields)
    return cfg.replace(network=dataclasses.replace(
        cfg.network, ouro=ou, compute_dtype=dtype))


def net_and_params(cfg, seed: int = 0):
    """The net with blocks of 4: a 20-token segment crosses four. The
    gate's weights of order 1, so that lambda is not one half
    everywhere."""
    net = OuroQNet(cfg.network.ouro, cfg.network.compute_dtype,
                   attn_blocks=BLOCKS)
    params = net.init(jax.random.PRNGKey(seed))
    gate = params["early_exit_gate"]
    gate["weight"] = gate["weight"] * 10.0
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


def reference_loss(cfg, online, target, items, w, sizes=None, **kw):
    """`online`/`target`: the REFERENCE's dicts."""
    return ref.loss_and_gradients(
        online, target, items["obs"], items["actions"], items["rewards"],
        items["terminals"], items["mask"], w,
        sizes=sizes or mapper.sizes(cfg.network.ouro),
        burn_in=cfg.replay.burn_in, n_step=cfg.learner.n_step,
        gamma=cfg.learner.gamma, eta=cfg.replay.priority_eta,
        huber_delta=cfg.learner.huber_delta, **kw)


def test_param_count_and_the_published_cut():
    cfg = tiny()
    net, params = net_and_params(cfg)
    assert net.param_count() == sum(x.size for x in jax.tree.leaves(params))
    assert set(params) == {"embed_tokens", "layers", "norm",
                           "early_exit_gate", "lm_head"}
    assert len(params["layers"]) == LAYERS      # ONE set, whatever the steps
    assert set(params["layers"][0]) == {
        "q_proj", "k_proj", "v_proj", "o_proj", "input_layernorm",
        "post_attention_layernorm", "pre_mlp_layernorm",
        "post_mlp_layernorm", "mlp"}
    # dense: no router, no experts
    assert set(params["layers"][0]["mlp"]) == {"gate_proj", "up_proj",
                                               "down_proj"}
    assert not hasattr(net, "share")
    assert not np.any(params["early_exit_gate"]["bias"])
    from ape_x_dqn_tpu.runtime.train import apply_overrides

    with open(CONFIG_FILE) as fh:
        conf = json.load(fh)
    cut = apply_overrides(get_config("ouro_2p6b_q"), conf["overrides"])
    big = build_network(cut.network, None)
    block = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert block == 51_388_416
    ends = 2 * 49_152 * 2048 + 2048 + 2049
    held = cut.network.ouro.num_hidden_layers
    assert big.param_count() == held * block + ends \
        == conf["model_sizes"]["parameters"]
    assert big.num_actions == 49_152
    whole = build_network(get_config("ouro_2p6b_q").network, None)
    assert whole.param_count() == 48 * block + ends == 2_667_974_657
    assert 6 * block + ends == 509_661_185
    assert 5 * block + ends == 458_272_769


@pytest.mark.parametrize("burn_in", [BURN, 0, 16])
def test_loss_and_gradients_match_reference_float32(burn_in):
    """Q, loss, priorities and every gradient leaf - the system's one
    leaf per looped weight against `jax.grad` of the reference's Python
    loop, which sums the four applications -, with a prefix that ends
    inside a block, none, and one that ends on a block's edge. The exit
    gate gets no gradient."""
    cfg = tiny()
    cfg = cfg.replace(replay=dataclasses.replace(cfg.replay,
                                                 burn_in=burn_in))
    net, params = net_and_params(cfg)
    _, target = net_and_params(cfg, seed=5)
    items, w = batch(cfg), jnp.asarray([1.0, 0.5, 0.7])
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        system_loss(cfg, net), has_aux=True))(params, target, items, w)
    (want, raux), rgrads = jax.jit(lambda p, t: reference_loss(
        cfg, mapper.reference_params(p), mapper.reference_params(t), items,
        w))(params, target)
    np.testing.assert_allclose(loss, want, atol=1e-5)
    np.testing.assert_allclose(aux["q"], raux["q"], atol=1e-5)
    np.testing.assert_allclose(aux["td_abs"], raux["priorities"], atol=1e-5)
    rgrads = mapper.system_gradients(rgrads, params[mapper.GATE])
    assert jax.tree.structure(grads) == jax.tree.structure(rgrads)
    for (path, got), exp in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree.leaves(rgrads)):
        np.testing.assert_allclose(got, exp, atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))
    assert not np.any(grads["early_exit_gate"]["weight"])
    assert not np.any(grads["early_exit_gate"]["bias"])
    assert np.any(grads["layers"][0]["q_proj"])
    assert int(aux["loop_block_applications"]) == STEPS * LAYERS


def test_four_copies_of_the_stack_add_up_to_the_looped_net():
    """THE TIE TO THE MODEL. A reference with FOUR separate copies of
    the stack, one per loop step (the same numbers in each): its forward
    is the looped net's, and the gradients its four copies receive -
    each application's own - add up, leaf by leaf, to the ONE gradient
    leaf the system's scan hands back for the shared weight. No copy's
    gradient is the whole (the sum is not one application's)."""
    cfg = tiny()
    net, params = net_and_params(cfg)
    _, target = net_and_params(cfg, seed=5)
    items, w = batch(cfg), jnp.asarray([1.0, 0.5, 0.7])
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        system_loss(cfg, net), has_aux=True))(params, target, items, w)
    online = mapper.reference_params(params)
    unrolled = {**online, "copies": [online["layers"]] * STEPS}
    del unrolled["layers"]
    (want, raux), rgrads = jax.jit(lambda p, t: reference_loss(
        cfg, p, t, items, w,
        layers_of_step=lambda net_, step: net_["copies"][step]))(
        unrolled, mapper.reference_params(target))
    np.testing.assert_allclose(loss, want, atol=1e-5)
    np.testing.assert_allclose(aux["q"], raux["q"], atol=1e-5)
    assert len(rgrads["copies"]) == STEPS
    for index in range(LAYERS):
        per_copy = [mapper.system_layer_gradients(rgrads["copies"][t][index])
                    for t in range(STEPS)]
        summed = jax.tree.map(lambda *g: sum(g), *per_copy)
        got = grads["layers"][index]
        assert jax.tree.structure(got) == jax.tree.structure(summed)
        for (path, g), s, first in zip(
                jax.tree_util.tree_flatten_with_path(got)[0],
                jax.tree.leaves(summed), jax.tree.leaves(per_copy[0])):
            name = jax.tree_util.keystr(path)
            np.testing.assert_allclose(g, s, atol=1e-5, err_msg=name)
            assert np.abs(np.asarray(g) - first).max() > 1e-4, name
    # every copy's share is its own: no two applications' agree
    q0, q3 = (rgrads["copies"][t][0]["wq"] for t in (0, 3))
    assert np.abs(np.asarray(q0) - q3).max() > 1e-4


def test_prefix_then_segment_through_the_cache_equals_one_pass():
    """The trained steps through the [steps][layers] cache the burn-in
    leaves equal the REFERENCE's one causal pass over the whole sequence
    (and the system's own); the cache holds one (k, v) per (loop step,
    layer), and no two steps' agree."""
    cfg = tiny()
    net, params = net_and_params(cfg)
    tokens = batch(cfg)["obs"]
    apply = jax.jit(net.apply)
    whole, _ = apply(params, tokens, ())
    _, state = apply(params, tokens[:, :BURN], ())
    segment, after = apply(params, tokens[:, BURN:], state)
    np.testing.assert_allclose(segment, whole[:, BURN:], atol=1e-5)
    want = jax.jit(lambda p: ref.forward(
        mapper.reference_params(p), tokens,
        mapper.sizes(cfg.network.ouro)))(params)
    np.testing.assert_allclose(segment, want[:, BURN:], atol=1e-5)
    assert len(state) == len(after) == LAYERS
    for (k, v, seen), (k2, v2, seen2) in zip(state, after):
        assert int(seen) == BURN and int(seen2) == L
        assert k.shape == v.shape == (STEPS, B, BURN, 4, 16)
        assert k2.shape == v2.shape == (STEPS, B, L, 4, 16)
        np.testing.assert_array_equal(k2[:, :, :BURN], k)
        for t in range(1, STEPS):
            assert np.abs(np.asarray(k[t]) - k[0]).max() > 1e-3
            assert np.abs(np.asarray(v[t]) - v[0]).max() > 1e-3


def test_positions_run_on_across_the_prefix_and_are_the_same_every_step():
    """One cached key per (step, layer), then one token: where the token
    stands changes its Q-values (RoPE against the cached keys)."""
    cfg = tiny()
    net, params = net_and_params(cfg)
    kv = jax.random.normal(jax.random.PRNGKey(7), (2, STEPS, 1, 1, 4, 16))
    apply = jax.jit(net.apply)

    def at(seen):
        state = tuple((kv[0], kv[1], jnp.int32(seen)) for _ in range(LAYERS))
        return apply(params, jnp.asarray([[5]]), state)[0]

    assert not np.allclose(at(1), at(5), atol=1e-6)
    np.testing.assert_allclose(at(3), at(3))


@pytest.mark.parametrize("departure", [
    {"loop_steps": 3}, {"prefix_from": "step_0"},
    {"final_norm": "after_loop"}, {"post_norms": False}])
def test_the_reference_tells_each_departure_apart(departure):
    """What the benchmark's check must refuse: against the reference
    with three loop steps for four, every step reading step 0's keys
    and values at the burn-in positions, the final norm once after the
    loop, or no post-sublayer norms, the system's Q-values are far
    outside rounding."""
    cfg = tiny()
    net, params = net_and_params(cfg)
    items, w = batch(cfg), jnp.ones(B)
    _, aux = jax.jit(system_loss(cfg, net))(params, params, items, w)
    sizes = mapper.sizes(cfg.network.ouro)

    def q_of(sz):
        (_, raux), _ = jax.jit(lambda p: reference_loss(
            cfg, p, p, items, w, sizes=sz))(mapper.reference_params(params))
        return np.asarray(raux["q"])

    np.testing.assert_allclose(aux["q"], q_of(sizes), atol=1e-5)
    off = np.abs(np.asarray(aux["q"]) - q_of(sizes._replace(**departure)))
    assert np.quantile(off, 0.95) > 100 * 1e-5


def _whiles(net, params, tokens, grad: bool) -> int:
    fn = ((lambda p: net.apply(p, tokens, ())[0].sum()) if not grad
          else jax.grad(lambda p: net.apply(p, tokens, ())[0].sum()))
    return jax.jit(fn).lower(params).as_text().count("stablehlo.while")


@pytest.mark.parametrize("grad", [False, True])
def test_the_loop_is_one_scan_in_the_lowered_program(grad):
    """One attention body per LAYER, not one per block application: the
    lowered text holds the scan over the loop steps and, inside it, each
    layer's blockwise attention (two nested loops forward) ONCE -
    whatever `total_ut_steps` is - and more of them with more layers."""
    tokens = jnp.asarray(batch(tiny())["obs"])
    counts = {}
    for steps, layers in ((4, 2), (2, 2), (4, 3)):
        net, params = net_and_params(tiny(total_ut_steps=steps,
                                          num_hidden_layers=layers))
        counts[steps, layers] = _whiles(net, params, tokens, grad)
    assert counts[4, 2] == counts[2, 2] < counts[4, 3]
    if not grad:
        assert counts[4, 2] == 1 + 2 * LAYERS


# -- where the net rounds ---------------------------------------------------

def test_held_is_the_casts_value_and_the_identity_in_float32():
    """`_held` gives what `astype` gives, and float32 compute (the tiny
    presets) is left as it is."""
    from ape_x_dqn_tpu.models.ouro_q import _held

    x = jnp.asarray(np.random.default_rng(0).normal(size=(64,)) * 3.0,
                    jnp.float32)
    held = _held(x, jnp.bfloat16)
    assert held.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(held, np.float32),
                                  np.asarray(x.astype(jnp.bfloat16),
                                             np.float32))
    assert _held(x, jnp.float32) is x


def test_a_held_roundings_cotangent_is_rounded_too():
    """What a system in bfloat16 computes: the cotangent that comes
    back through a rounding has bfloat16's 8 bits."""
    from ape_x_dqn_tpu.models.ouro_q import _held

    x = jnp.linspace(0.1, 1.0, 32, dtype=jnp.float32)
    weight = jnp.asarray(np.random.default_rng(1).normal(size=(32,)),
                         jnp.float32)
    got = jax.grad(lambda a: (_held(a, jnp.bfloat16).astype(jnp.float32)
                              * weight).sum())(x)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(weight.astype(jnp.bfloat16), np.float32))


@pytest.mark.parametrize("dtype,kept", [("bfloat16", True),
                                        ("float32", False)])
def test_every_rounding_of_a_block_is_one_xla_keeps(dtype, kept):
    """Below float32 the lowered forward pass holds a `reduce_precision`
    for each value a block rounds (4 norms, 7 products, 2 rotations,
    silu, the gated product, 2 residual sums = 17 a block; the scan's
    body holds each layer once), the loop's closing norm and the
    embedding; a plain `astype` is a rounding XLA:TPU takes back where
    the reader converts to float32 again (PERF.md section 6, PR 41)."""
    cfg = tiny(dtype)
    net, params = net_and_params(cfg)
    tokens = jnp.asarray(batch(cfg)["obs"])
    text = jax.jit(lambda p: net.apply(p, tokens, ())[0]).lower(
        params).as_text()
    assert text.count("stablehlo.reduce_precision") == (
        17 * LAYERS + 2 if kept else 0)


def test_the_exit_gate_feeds_the_counter_alone():
    """lambda_t = sigmoid(w_g . h^t + b_g) at every step, on the
    reference's h^t; the family's `loop_exit_mass_last` is the mean over
    the trained tokens of prod_{t<4} (1 - lambda_t); Q does not move
    with the gate's weights."""
    cfg = tiny()
    net, params = net_and_params(cfg)
    items, w = batch(cfg), jnp.ones(B)
    tokens = items["obs"]
    q, _, stats = jax.jit(net.apply_with_stats)(params, tokens, ())
    assert stats["exit_gates"].shape == (STEPS, B, L)
    assert int(stats["block_applications"]) == STEPS * LAYERS
    rp, sz = mapper.reference_params(params), mapper.sizes(cfg.network.ouro)
    x, gate = ref.embed(rp, tokens), params["early_exit_gate"]
    for step in range(STEPS):
        for p in rp["layers"]:
            x, _ = ref.block(p, x, sz)
        x = ref.end_of_step(rp, x, sz, step)
        lam = jax.nn.sigmoid(x @ gate["weight"] + gate["bias"])[..., 0]
        np.testing.assert_allclose(stats["exit_gates"][step], lam, atol=1e-5)
    assert 0.05 < float(stats["exit_gates"].std())      # not one half
    _, aux = jax.jit(system_loss(cfg, net))(params, params, items, w)
    # the loss's trained pass runs from the prefix's cache: the same
    # states, so the same gates at the trained positions
    stay = np.prod(1.0 - np.asarray(stats["exit_gates"])[:-1], axis=0)
    np.testing.assert_allclose(aux["loop_exit_mass_last"],
                               stay[:, BURN:].mean(), atol=1e-5)
    other = {**params, "early_exit_gate": jax.tree.map(
        lambda a: a + 1.0, params["early_exit_gate"])}
    np.testing.assert_array_equal(jax.jit(net.apply)(other, tokens, ())[0], q)


def test_the_fourth_net_is_a_row_and_has_no_share():
    cfg = get_config("ouro_tiny_q")
    net = build_network(cfg.network, None)
    assert type(net) is DECODER_NETS["ouro_q"] is OuroQNet
    name, block = decoder_block(cfg.network)
    assert name == "ouro" and block is cfg.network.ouro
    assert (block.total_ut_steps, block.num_hidden_layers) == (STEPS, LAYERS)
    assert block.num_attention_heads == block.num_key_value_heads


def test_family_rows():
    cfg = tiny()
    assert family_of(cfg) == "decoder_q"
    assert ACTOR_STATE["decoder_q"].stored == ()
    assert stored_state_spec("decoder_q", cfg) == {}
    net = build_network(cfg.network, make_env(cfg.env).spec)
    assert type(net) is OuroQNet and net.num_actions == 64
    with pytest.raises(NotImplementedError, match="early_exit_threshold"):
        OuroQNet(dataclasses.replace(cfg.network.ouro,
                                     early_exit_threshold=0.9))
    with pytest.raises(ValueError, match="total_ut_steps"):
        dataclasses.replace(cfg.network.ouro, total_ut_steps=0)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        net.apply(net.init(jax.random.PRNGKey(0)),
                  jnp.zeros((1, 33), jnp.int32), ())


def test_env_and_family_must_agree_on_the_vocabulary():
    from ape_x_dqn_tpu.runtime.driver import ApexDriver

    cfg = tiny()
    cfg = cfg.replace(env=dataclasses.replace(cfg.env, num_tokens=32))
    with pytest.raises(ValueError, match="network.ouro.vocab_size"):
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
        assert type(driver.net) is OuroQNet
        state = driver.state
        rng = np.random.default_rng(0)
        n = 16
        items = {"obs": rng.integers(0, 64, (n, L)).astype(np.int32),
                 "actions": rng.integers(0, 64, (n, L)).astype(np.int32),
                 "rewards": rng.normal(size=(n, L)).astype(np.float32),
                 "terminals": np.zeros((n, L), np.float32),
                 "mask": np.ones((n, L), np.float32)}
        state = driver.learner.add(state, items, jnp.ones(n))
        before = jax.device_get(state.params)
        state, m = driver.learner.train_many(state, 2)
        assert int(state.step) == 2 and np.isfinite(float(m["loss"]))
        assert not any(k.startswith("moe_") for k in m)
        assert float(m["loop_block_applications"]) == STEPS * LAYERS
        assert 0.0 < float(m["loop_exit_mass_last"]) < 1.0
        assert np.isfinite(float(m["valid_frac"]))
        after = jax.device_get(state.params)
        for name in ("q_proj", "o_proj"):
            assert not np.array_equal(before["layers"][1][name],
                                      after["layers"][1][name])
        assert not np.array_equal(before["layers"][0]["mlp"]["up_proj"],
                                  after["layers"][0]["mlp"]["up_proj"])
        # the gate feeds a counter: no gradient, so Adam leaves it
        np.testing.assert_array_equal(before["early_exit_gate"]["weight"],
                                      after["early_exit_gate"]["weight"])
    finally:
        driver.server.stop()


def test_train_run_with_actors_completes(tmp_path):
    from ape_x_dqn_tpu.runtime import train

    out = tmp_path / "m.jsonl"
    argv = ["--config", "ouro_tiny_q", "--actors", "2",
            "--max-grad-steps", "8", "--wall-clock-limit", "120",
            "--metrics-file", str(out), "--set", "eval_episodes=1",
            "--set", "eval_max_frames=100", "--set", "eval_every_steps=0"]
    assert train.main(argv) == 0
    assert os.path.getsize(out) > 0


def test_hbm_budget_admits_the_cut_and_refuses_the_whole_model():
    from ape_x_dqn_tpu.runtime.family import hbm_price
    from ape_x_dqn_tpu.runtime.train import apply_overrides
    from ape_x_dqn_tpu.utils import hbm

    v5e = int(15.75 * 1024 ** 3)

    def check(cfg):
        net = build_network(cfg.network, None)
        return hbm.check_hbm_fits(
            cfg, (), np.int32, param_count=net.param_count(),
            hbm_bytes=v5e, **hbm_price(cfg, net))

    whole = get_config("ouro_2p6b_q")
    with pytest.raises(ValueError, match="GiB per device"):
        check(whole)
    with open(CONFIG_FILE) as fh:
        conf = json.load(fh)
    cut = apply_overrides(whole, conf["overrides"])
    budget = check(cut)
    # the net's own price of a step against the compiled reading
    held = cut.network.ouro.num_hidden_layers
    compiled = conf["memory"][f"{held}_layers"]["temp"]
    assert 0.85 * compiled <= budget.headroom / 2 ** 30 <= 1.25 * compiled
