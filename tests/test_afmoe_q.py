"""The decoder family's second net (network.kind "afmoe_q") at tiny widths
on the CPU: hidden 64, 4 query / 2 key-value heads of 16, 8 experts in 2
shards, a vocabulary of 64 in 2 slices, A WINDOW OF 8 inside sequences of
32 with a burn-in of 12 and attention blocks of 4, so that the window,
the trimmed cache, the prefix boundary and the block boundaries all
bite. The net against benchmarks/reference/afmoe_q.py (Q, loss,
gradients; tests/test_blockwise_attention.py holds the attention to
dense masked attention); the two kinds of cache; the shares add up; the expert layer both decoder
nets call; the family's rows build through ApexDriver; a run with
actors completes; the HBM budget admits the chip's share and refuses
the whole model."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.configs import get_config
from ape_x_dqn_tpu.envs import make_env
from ape_x_dqn_tpu.models import (DECODER_NETS, build_network,
                                  decoder_block)
from ape_x_dqn_tpu.models.afmoe_q import AfmoeQNet
from ape_x_dqn_tpu.models.expert_layer import ExpertShare, expert_ffn
from ape_x_dqn_tpu.runtime.family import (
    ACTOR_STATE, family_of, learner_family, stored_state_spec)
from benchmarks.harness import afmoe_params, glm_params
from benchmarks.reference import afmoe_q as ref
from benchmarks.reference import glm_moe_q as glm_ref

L, BURN, B, WINDOW = 32, 12, 3, 8
BLOCKS = (4, 4)


def tiny(shards: int = 2, index: int = 0, dtype: str = "float32",
         balanced: bool = False, **fields):
    cfg = get_config("trinity_tiny_q")
    afmoe = dataclasses.replace(
        cfg.network.afmoe, shard_count=shards, shard_index=index,
        force_balanced_routing=balanced, **fields)
    return cfg.replace(
        network=dataclasses.replace(cfg.network, afmoe=afmoe,
                                    compute_dtype=dtype),
        env=dataclasses.replace(cfg.env,
                                num_tokens=afmoe.vocab_size // shards))


def net_and_params(cfg, seed: int = 0):
    """The net with blocks of 4: a 20-token segment crosses four."""
    net = AfmoeQNet(cfg.network.afmoe, cfg.network.compute_dtype,
                    attn_blocks=BLOCKS)
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


def reference_loss(cfg, net, params, target, items, w, **kw):
    return ref.loss_and_gradients(
        afmoe_params.reference_params(params),
        afmoe_params.reference_params(target), items["obs"],
        items["actions"], items["rewards"], items["terminals"],
        items["mask"], w,
        sizes=afmoe_params.sizes(cfg.network.afmoe, net.router_trains),
        burn_in=cfg.replay.burn_in, n_step=cfg.learner.n_step,
        gamma=cfg.learner.gamma, eta=cfg.replay.priority_eta,
        huber_delta=cfg.learner.huber_delta, **kw)


def test_param_count_and_the_published_share():
    cfg = tiny()
    net, params = net_and_params(cfg)
    assert net.param_count() == sum(x.size for x in jax.tree.leaves(params))
    assert set(params["layers"][0]) >= {
        "q_proj", "k_proj", "v_proj", "gate_proj", "o_proj", "q_norm",
        "k_norm", "input_layernorm", "post_attention_layernorm",
        "pre_mlp_layernorm", "post_mlp_layernorm"}
    from ape_x_dqn_tpu.runtime.train import apply_overrides

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "trinity_mini_ep16_1chip.json")) as fh:
        import json
        overrides = json.load(fh)["overrides"]
    share = apply_overrides(get_config("trinity_mini_q"), overrides)
    big = build_network(share.network, None)
    assert big.param_count() == 504_147_712
    assert (big.num_actions, big.experts_held) == (25_024, 8)
    assert not big.router_trains


@pytest.mark.parametrize("shards,index,balanced", [
    (1, 0, False), (2, 0, False), (2, 1, False), (2, 0, True)])
def test_loss_and_gradients_match_reference_float32(shards, index, balanced):
    """Q, loss, priorities, the selection and every gradient leaf, with
    a window shorter than the sequence and a prefix longer than it."""
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
    rgrads = afmoe_params.system_gradients(rgrads)
    for got, exp in zip(jax.tree.leaves(grads), jax.tree.leaves(rgrads)):
        np.testing.assert_allclose(got, exp, atol=1e-5)
    # the selection bias never trains; in a share neither does the router
    moe = grads["layers"][1]["mlp"]
    assert not np.any(moe["e_score_correction_bias"])
    assert bool(np.any(moe["gate"])) == (shards == 1)


@pytest.mark.parametrize("selection", ["forced", "own"])
def test_compact_share_says_which_steps_paid_the_full_width(selection):
    """tests/test_glm_moe_q.py's case through this net: 16 sequences,
    the trained segment's 640 assignments in buffers of 512 rows."""
    from ape_x_dqn_tpu.models.expert_layer import capacity
    from tests.test_glm_moe_q import big_batch, selecting_only_held

    count = 16
    cfg = tiny(balanced=selection == "forced")
    net, params = net_and_params(cfg)
    _, target = net_and_params(cfg, seed=5)
    if selection == "own":
        params = selecting_only_held(params, net)
        target = selecting_only_held(target, net)
    k = net.share.top_k
    assert capacity(net.share, count * (L - BURN)) < k * count * (L - BURN)
    assert capacity(net.share, count * BURN) == k * count * BURN
    items = big_batch(cfg, count, L)
    w = jnp.ones(count)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        system_loss(cfg, net), has_aux=True))(params, target, items, w)
    (want, raux), rgrads = jax.jit(
        lambda p, t: reference_loss(cfg, net, p, t, items, w))(params, target)
    np.testing.assert_allclose(loss, want, atol=1e-5)
    np.testing.assert_allclose(aux["td_abs"], raux["priorities"], atol=1e-5)
    rgrads = afmoe_params.system_gradients(rgrads)
    for got, exp in zip(jax.tree.leaves(grads), jax.tree.leaves(rgrads)):
        np.testing.assert_allclose(got, exp, atol=1e-5)
    assert float(aux["moe_compact_share"]) == (
        1.0 if selection == "forced" else 0.5)


def test_bfloat16_stays_in_a_stated_band():
    """bfloat16 compute against the float32 reference forced to the
    system's selection: Q within 6% of its spread (8 bits of mantissa
    through 3 layers; read 1.5-2.5%), which a wrong mask or cache (a
    whole key's weight) is far outside of."""
    cfg = tiny(dtype="bfloat16")
    net, params = net_and_params(cfg)
    items, w = batch(cfg), jnp.ones(B)
    _, aux = jax.jit(system_loss(cfg, net))(params, params, items, w)
    (_, raux), _ = jax.jit(lambda p, on, tg: reference_loss(
        cfg, net, p, p, items, w, forced_online=on, forced_target=tg))(
        params, aux["topk_online"], aux["topk_target"])
    err = np.abs(np.asarray(aux["q"], np.float32) - raux["q"])
    assert np.quantile(err, 0.95) < 0.06 * float(np.std(raux["q"]))


def test_prefix_then_segment_through_the_cache_equals_one_pass():
    """Values: the trained steps through the two kinds of cache the
    burn-in leaves equal one causal pass over the whole sequence.
    Gradient: the system's loss (prefix pass, state stopped, segment)
    equals the reference's one pass with the gradient stopped at the
    prefix's keys and values - test_loss_and_gradients_match_reference
    holds that; here the cache's shapes and the values."""
    cfg = tiny()
    net, params = net_and_params(cfg)
    tokens = batch(cfg)["obs"]
    apply = jax.jit(net.apply)
    whole, _ = apply(params, tokens, ())
    _, state = apply(params, tokens[:, :BURN], ())
    segment, after = apply(params, tokens[:, BURN:], state)
    np.testing.assert_allclose(segment, whole[:, BURN:], atol=1e-5)
    kinds = cfg.network.afmoe.layer_types
    for kind, (k, v, seen), (k2, _, seen2) in zip(kinds, state, after):
        assert int(seen) == BURN and int(seen2) == L
        held = WINDOW - 1 if kind == "sliding_attention" else BURN
        assert k.shape == v.shape == (B, held, 2, 16)
        assert k2.shape[1] == (WINDOW - 1 if kind == "sliding_attention"
                               else L)


def test_a_longer_sliding_cache_changes_nothing():
    """A sliding layer keeps window - 1 positions because no later query
    reaches further back: the same segment after the UNTRIMMED cache
    (every prefix position) gives the same values. The untrimmed cache
    is put together from two passes: a prefix of window - 1 tokens is
    not trimmed yet and holds positions 0 .. 6 (causality: the values
    the whole prefix gives them), the whole prefix's holds 5 .. 11."""
    cfg = tiny(layer_types=("sliding_attention",) * 3)
    net, params = net_and_params(cfg)
    tokens = batch(cfg)["obs"]
    apply = jax.jit(net.apply)
    _, state = apply(params, tokens[:, :BURN], ())
    trimmed, _ = apply(params, tokens[:, BURN:], state)
    _, first = apply(params, tokens[:, :WINDOW - 1], ())
    dropped = BURN - (WINDOW - 1)
    untrimmed = tuple(
        (jnp.concatenate([k_a[:, :dropped], k_b], axis=1),
         jnp.concatenate([v_a[:, :dropped], v_b], axis=1), seen)
        for (k_a, v_a, _), (k_b, v_b, seen) in zip(first, state))
    assert all(k.shape[1] == BURN for k, _, _ in untrimmed)
    longer, _ = apply(params, tokens[:, BURN:], untrimmed)
    np.testing.assert_allclose(longer, trimmed, atol=1e-6)


def test_full_layers_are_position_free_and_sliding_ones_are_not():
    """One cached key, then one token: where the token stands (`seen`,
    which the cache carries because a trimmed cache no longer says)
    changes its Q-values through RoPE's rotation against the cached key
    if the layers are sliding, and changes nothing if they are full (no
    position encoding at all)."""
    for kinds, moved in ((("full_attention",) * 3, False),
                         (("sliding_attention",) * 3, True)):
        cfg = tiny(layer_types=kinds)
        net, params = net_and_params(cfg)
        kv = jax.random.normal(jax.random.PRNGKey(7), (2, 1, 1, 2, 16))
        apply = jax.jit(net.apply)

        def at(seen):
            state = tuple((kv[0], kv[1], jnp.int32(seen)) for _ in kinds)
            return apply(params, jnp.asarray([[5]]), state)[0]

        differs = not np.allclose(at(1), at(5), atol=1e-6)
        assert differs == moved, kinds


def test_the_shares_add_up():
    """The routed parts that the two shares compute, with the shared
    expert counted once, add up to what the uncut reference gives for
    the whole layer."""
    whole = tiny(shards=1)
    net1, params1 = net_and_params(whole)
    layer = params1["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(3), (B, L, 64))
    sizes = afmoe_params.sizes(whole.network.afmoe)
    ref_layer = afmoe_params.reference_params(params1)["layers"][1]
    want, _, _ = glm_ref.expert_layer(ref_layer, x, sizes, None, lambda a: a)
    shared = glm_ref.swiglu(x, ref_layer["shared"], lambda a: a)
    total, rows = jnp.zeros_like(want), 0
    for index in range(2):
        net, _ = net_and_params(tiny(shards=2, index=index))
        held = net.experts_held
        mlp = dict(layer["mlp"])
        mlp["experts"] = {k: v[index * held:(index + 1) * held]
                          for k, v in layer["mlp"]["experts"].items()}
        out, n, _ = expert_ffn(mlp, x, jnp.float32, net.share)
        total = total + (out - shared)
        rows += int(n.sum())
    np.testing.assert_allclose(total + shared, want, atol=1e-5)
    assert rows == B * L * whole.network.afmoe.num_experts_per_tok


@pytest.mark.parametrize("kind", ["afmoe_q", "glm_moe_q"])
def test_one_expert_layer_serves_both_decoder_nets(kind):
    """Each net hands models/expert_layer.py its own numbers and gets
    what the reference's expert layer gives at those numbers - the
    answers the layer gave GLM before it moved (tests/test_glm_moe_q.py
    holds GLM's whole net to its reference as before; the third net's
    case, with its own scoring, is tests/test_smallthinker_q.py's)."""
    preset = {"glm_moe_q": "glm_tiny_q", "afmoe_q": "trinity_tiny_q"}[kind]
    cfg = get_config(preset)
    net = build_network(cfg.network, None)
    assert isinstance(net.share, ExpertShare)
    assert type(net) is DECODER_NETS[kind]
    params = net.init(jax.random.PRNGKey(0))
    index = next(i for i, p in enumerate(params["layers"])
                 if "experts" in p["mlp"])
    mapper = {"glm_moe_q": glm_params, "afmoe_q": afmoe_params}[kind]
    _, block = decoder_block(cfg.network)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, block.hidden_size))
    got, rows, ids = expert_ffn(params["layers"][index]["mlp"], x,
                                jnp.float32, net.share)
    want, own, _ = glm_ref.expert_layer(
        mapper.reference_layer(params, index), x, mapper.sizes(block), None,
        lambda a: a)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert (np.sort(ids, -1) == np.sort(own, -1)).all()
    assert int(rows.sum()) == 2 * 9 * net.share.top_k


@pytest.mark.parametrize("experts", [8, 64, 128])
def test_forced_selection_scores_are_distinct_and_the_references(experts):
    """The forced selection's scores at this model's 128 experts: no two
    of a token's scores equal, exact in float32, and bit for bit the
    reference's (up to 64 experts the id takes 6 bits, as GLM's cell
    has it; 128 need 7 - with 6, an OR and a sum of overlapping bits
    differ and the v5e's check found 6 selections of 65,536 apart)."""
    from ape_x_dqn_tpu.models.expert_layer import _balanced_scores

    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 25_024, (2, 512)), jnp.int32)
    got = np.asarray(_balanced_scores(tokens, jnp.arange(512), 3, experts))
    np.testing.assert_array_equal(
        got, ref.balanced_scores(tokens, 3, experts))
    assert got.max() < 2 ** 24
    assert all(len(set(row)) == experts for row in got.reshape(-1, experts))
    if experts <= 64:
        np.testing.assert_array_equal(
            got, glm_ref.balanced_scores(tokens, 3, experts))


def test_family_rows():
    cfg = tiny()
    assert family_of(cfg) == "decoder_q"
    assert ACTOR_STATE["decoder_q"].stored == ()
    assert stored_state_spec("decoder_q", cfg) == {}
    net = build_network(cfg.network, make_env(cfg.env).spec)
    assert type(net) is AfmoeQNet and net.num_actions == 32
    with pytest.raises(ValueError, match="one kind for each layer"):
        AfmoeQNet(dataclasses.replace(cfg.network.afmoe,
                                      num_hidden_layers=2))


def test_env_and_family_must_agree_on_the_vocabulary():
    from ape_x_dqn_tpu.runtime.driver import ApexDriver

    cfg = tiny()
    cfg = cfg.replace(env=dataclasses.replace(cfg.env, num_tokens=64))
    with pytest.raises(ValueError, match="network.afmoe.vocab_size"):
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
        assert type(driver.net) is AfmoeQNet
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
        np.testing.assert_array_equal(
            before["mlp"]["e_score_correction_bias"],
            after["mlp"]["e_score_correction_bias"])
        np.testing.assert_array_equal(before["mlp"]["gate"],
                                      after["mlp"]["gate"])
    finally:
        driver.server.stop()


def test_train_run_with_actors_completes(tmp_path):
    from ape_x_dqn_tpu.runtime import train

    out = tmp_path / "m.jsonl"
    argv = ["--config", "trinity_tiny_q", "--actors", "2",
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

    whole = get_config("trinity_mini_q")
    assert build_network(whole.network, None).param_count() > 25e9
    with pytest.raises(ValueError, match="GiB per device"):
        check(whole)
    kinds = ("sliding_attention",) * 4 + ("full_attention",)
    share = apply_overrides(whole, [
        "network.afmoe.num_hidden_layers=5",
        "network.afmoe.num_dense_layers=1",
        f"network.afmoe.layer_types={kinds!r}",
        "network.afmoe.shard_count=16",
        "network.afmoe.vocab_shard_count=8", "env.num_tokens=25024"])
    budget = check(share)
    gib = 1024 ** 3
    assert budget.model_state == 16 * 504_147_712
    # 4,096 sequences x (5 x 8,192 x 4 B): no state entry is priced
    assert budget.replay_storage == 4_096 * 163_840
    # the compiled step's temp reads 4.28 GiB (PERF.md section 4)
    assert 4.2 < budget.headroom / gib < 4.4
    # the server's own copy of the parameters (1.88 GiB) is not priced
    assert 12.0 < budget.total / gib < 15.75 - 1.88
    # a fifth expert layer does not fit beside that copy
    more = check(apply_overrides(share, [
        "network.afmoe.num_hidden_layers=6",
        f"network.afmoe.layer_types={kinds + ('sliding_attention',)!r}"]))
    assert more.total / gib > 15.75 - 1.88 - 0.39
