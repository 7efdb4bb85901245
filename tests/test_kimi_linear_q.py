"""The decoder family's fifth net (network.kind "kimi_linear_q") at tiny
widths on the CPU: hidden 48, every kind of layer (KDA + dense FFN, KDA
+ experts, MLA + experts, KDA + experts), 3 KDA heads of 8 (no KDA width
is hidden / heads), 2 MLA heads with keys of 12 + 4 and values of 8, 8
experts top-2, a vocabulary of 64, sequences of 32 with a burn-in of 12,
CHUNKS OF 8 and attention blocks of 4, so that the prefix boundary
falls inside a chunk and a block and the trained segment's 20 positions
are off a chunk. The net against benchmarks/reference/kimi_linear_q.py
(Q, loss, priorities, every gradient leaf), forced and unforced
selection; the burn-in through both kinds of state against one pass;
the shares add up; the four departures the reference can make are seen;
`kda_chunks` reads layers x positions / C; the family's rows build
through ApexDriver; a run with actors completes; the HBM budget admits
the chip's share and refuses the whole model."""

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
from ape_x_dqn_tpu.models.expert_layer import SELECTION, expert_ffn
from ape_x_dqn_tpu.models.kimi_linear_q import KimiLinearQNet
from ape_x_dqn_tpu.runtime.family import (
    ACTOR_STATE, family_of, hbm_price, learner_family, stored_state_spec)
from benchmarks.harness import kimi_linear_params as mapper
from benchmarks.reference import afmoe_q as afmoe_ref
from benchmarks.reference import glm_moe_q as glm_ref
from benchmarks.reference import kimi_linear_q as ref

L, BURN, B, CHUNK = 32, 12, 3, 8
BLOCKS = (4, 4)
CONFIG_FILE = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "kimi_linear_48b_ep32_1chip.json")


def tiny(shards: int = 2, index: int = 0, dtype: str = "float32",
         balanced: bool = False, **fields):
    cfg = get_config("kimi_linear_tiny_q")
    kl = dataclasses.replace(
        cfg.network.kimi_linear, shard_count=shards, shard_index=index,
        force_balanced_routing=balanced, **fields)
    return cfg.replace(
        network=dataclasses.replace(cfg.network, kimi_linear=kl,
                                    compute_dtype=dtype),
        env=dataclasses.replace(cfg.env,
                                num_tokens=kl.vocab_size // shards))


def net_and_params(cfg, seed: int = 0):
    """The net with chunks of 8 and blocks of 4."""
    net = KimiLinearQNet(cfg.network.kimi_linear, cfg.network.compute_dtype,
                         attn_blocks=BLOCKS, kda_chunk=CHUNK)
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
        sizes=sizes or mapper.sizes(cfg.network.kimi_linear,
                                    net.router_trains),
        burn_in=cfg.replay.burn_in, n_step=cfg.learner.n_step,
        gamma=cfg.learner.gamma, eta=cfg.replay.priority_eta,
        huber_delta=cfg.learner.huber_delta, **kw)


def test_param_count_and_the_published_share():
    cfg = tiny()
    net, params = net_and_params(cfg)
    assert net.param_count() == sum(x.size for x in jax.tree.leaves(params))
    assert net.layer_kinds == ("kda", "kda", "mla", "kda")
    kda, mla = params["layers"][1], params["layers"][2]
    assert {"A_log", "dt_bias", "q_conv1d", "f_a_proj", "g_b_proj",
            "o_norm"} <= set(kda) and "kv_b_proj" not in kda
    # no low-rank query, no q norm
    assert {"q_proj", "kv_a_proj_with_mqa", "kv_a_layernorm", "kv_b_proj",
            "o_proj"} <= set(mla) and "q_a_proj" not in mla
    assert "experts" not in params["layers"][0]["mlp"]
    assert set(params["layers"][1]["mlp"]) == {
        "gate", "e_score_correction_bias", "experts", "shared_experts"}
    # the decay's two parameters are seeded in their ranges
    a = np.exp(np.asarray(kda["A_log"]))
    dt = np.log1p(np.exp(np.asarray(kda["dt_bias"])))
    assert (1.0 <= a).all() and (a <= 16.0).all()
    assert (0.99e-3 <= dt).all() and (dt <= 1.01e-1).all()
    from ape_x_dqn_tpu.runtime.train import apply_overrides

    with open(CONFIG_FILE) as fh:
        overrides = json.load(fh)["overrides"]
    share = apply_overrides(get_config("kimi_linear_48b_q"), overrides)
    big = build_network(share.network, None)
    assert big.param_count() == 602_434_432
    assert (big.num_actions, big.experts_held) == (20_480, 8)
    assert big.layer_kinds == ("kda", "kda", "kda", "mla", "kda")
    assert not big.router_trains


@pytest.mark.parametrize("shards,index,balanced", [
    (1, 0, False), (2, 1, False), (8, 3, False), (1, 0, True), (2, 0, True)])
def test_loss_and_gradients_match_reference_float32(shards, index, balanced):
    """Q, loss, priorities, the selection and every gradient leaf - the
    chunked delta rule against the recurrence one position at a time,
    the blockwise latent attention against a materialised softmax -
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
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), exp in zip(flat, jax.tree.leaves(rgrads)):
        np.testing.assert_allclose(got, exp, atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))
    for layer in grads["layers"][1:]:
        assert bool(np.any(layer["mlp"]["gate"])) == (shards == 1)
    # every KDA parameter is live
    for name, g in grads["layers"][1].items():
        if name != "mlp":
            assert np.any(g), name


def test_prefix_then_segment_through_the_state_equals_one_pass():
    """The trained steps from the two kinds of state the burn-in leaves
    equal the REFERENCE's one pass over the whole sequence (and the
    system's own): a KDA layer's state is the same size after 12
    positions and after 32, an MLA layer's has a row per position."""
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
        mapper.sizes(cfg.network.kimi_linear)))(params)
    np.testing.assert_allclose(segment, want[:, BURN:], atol=1e-5)
    for kind, first, second, one_pass in zip(net.layer_kinds, state, after,
                                             whole_state):
        if kind == "kda":
            (s, tail, seen), (s2, tail2, seen2) = first, second
            assert s.shape == s2.shape == (B, 3, 8, 8)
            assert s.dtype == jnp.float32
            assert tail.shape == tail2.shape == (B, 3, 3, 24)
            assert (int(seen), int(seen2)) == (BURN, L)
        else:
            (c_kv, k_r), (c_kv2, k_r2) = first, second
            assert c_kv.shape == (B, BURN, 16) and k_r.shape == (B, BURN, 4)
            assert c_kv2.shape == (B, L, 16) and k_r2.shape == (B, L, 4)
        for a, b in zip(jax.tree.leaves(second), jax.tree.leaves(one_pass)):
            np.testing.assert_allclose(a, b, atol=1e-5)


def test_a_window_shorter_than_the_convolution_and_off_a_chunk():
    """The server's `apply_window` sends windows of any length: one
    token, then two more, through the state, equal three at once."""
    cfg = tiny()
    net, params = net_and_params(cfg)
    tokens = batch(cfg)["obs"][:, :3]
    whole, _ = net.apply(params, tokens, ())
    q1, state = net.apply(params, tokens[:, :1], ())
    q2, _ = net.apply(params, tokens[:, 1:], state)
    np.testing.assert_allclose(jnp.concatenate([q1, q2], axis=1), whole,
                               atol=1e-5)


def test_mla_layers_are_position_free_and_kda_layers_carry_order():
    """No rotation anywhere: with the MLA layer alone (its prefix keys
    permuted) nothing changes; a KDA layer's state does depend on the
    order of the prefix."""
    cfg = tiny()
    net, params = net_and_params(cfg)
    tokens = batch(cfg)["obs"]
    _, state = net.apply(params, tokens[:, :BURN], ())
    perm = np.random.default_rng(0).permutation(BURN)
    c_kv, k_r = state[2]
    shuffled = list(state)
    shuffled[2] = (c_kv[:, perm], k_r[:, perm])
    q_a, _ = net.apply(params, tokens[:, BURN:], state)
    q_b, _ = net.apply(params, tokens[:, BURN:], tuple(shuffled))
    np.testing.assert_allclose(q_a, q_b, atol=1e-5)
    _, other = net.apply(params, tokens[:, :BURN][:, perm], ())
    assert not np.allclose(state[0][0], other[0][0], atol=1e-4)


def _kimi_whole_layer():
    """-> (an expert layer's parameters of the uncut net, the net of a
    share, what the uncut reference gives for x, the shared expert's
    part of it, hidden, experts a token): sigmoid scores, top-2 of
    score + bias, normalised x 2.446 - GLM's arithmetic at this net's
    numbers - with one shared expert."""
    whole = tiny(shards=1)
    _, params = net_and_params(whole)
    sizes = mapper.sizes(whole.network.kimi_linear)
    ref_layer = mapper.reference_layer(params, 1)

    def want(x):
        out, _, _ = afmoe_ref.expert_layer(ref_layer, x, sizes, None,
                                           lambda a: a)
        return out, glm_ref.swiglu(x, ref_layer["shared"], lambda a: a)

    def share(ways, index):
        net, _ = net_and_params(tiny(shards=ways, index=index))
        assert net.share.scale == 2.446 and net.share.norm_topk
        return net

    return (params["layers"][1]["mlp"], share, want, 48,
            whole.network.kimi_linear.num_experts_per_token)


def _lfm2_whole_layer():
    """The decoder family's sixth net (models/lfm2_moe_q.py): sigmoid
    scores, top-2 of score + bias, normalised x 1, NO shared expert."""
    from ape_x_dqn_tpu.models.lfm2_moe_q import Lfm2MoeQNet
    from benchmarks.harness import lfm2_params
    from benchmarks.reference import lfm2_moe_q as lfm2_ref

    lf = get_config("lfm2_tiny_q").network.lfm2_moe
    params = Lfm2MoeQNet(lf, "float32").init(jax.random.PRNGKey(0))
    # times 8: at hidden 32 a matrix of normal(0, 0.02) leaves an
    # expert's output a thousandth of its input
    params["layers"] = jax.tree.map(
        lambda a: 8.0 * a if a.ndim >= 2 else a, params["layers"])
    sizes = lfm2_params.sizes(lf)
    ref_layer = lfm2_params.reference_layer(params, 1)

    def want(x):
        out, _, _ = lfm2_ref.expert_layer(ref_layer, x, sizes, None,
                                          lambda a: a)
        return out, jnp.zeros_like(out)

    def share(ways, index):
        net = Lfm2MoeQNet(dataclasses.replace(
            lf, shard_count=ways, shard_index=index), "float32")
        assert net.share.scale == 1.0 and net.share.norm_topk
        return net

    assert "shared_experts" not in params["layers"][1]["mlp"]
    return (params["layers"][1]["mlp"], share, want, lf.hidden_size,
            lf.num_experts_per_tok)


@pytest.mark.parametrize("ways", [2, 8])
@pytest.mark.parametrize("whole_layer", [_kimi_whole_layer,
                                         _lfm2_whole_layer],
                         ids=["kimi_linear", "lfm2_moe"])
def test_the_shares_add_up(whole_layer, ways):
    """The routed parts that the shares compute, with the shared expert
    (where the model has one) counted once, add up to what the uncut
    reference gives for the whole layer - for both nets that came with
    a share after the family's layer was made general."""
    layer, share, want_of, hidden, top_k = whole_layer()
    x = jax.random.normal(jax.random.PRNGKey(3), (B, L, hidden))
    want, shared = want_of(x)
    total, rows = jnp.zeros_like(want), 0
    for index in range(ways):
        net = share(ways, index)
        held = net.experts_held
        mlp = dict(layer)
        mlp["experts"] = {k: v[index * held:(index + 1) * held]
                          for k, v in layer["experts"].items()}
        out, n, _ = expert_ffn(mlp, x, jnp.float32, net.share)
        total = total + (out - shared)
        rows += int(n.sum())
    np.testing.assert_allclose(total + shared, want, atol=1e-5)
    assert rows == B * L * top_k


@pytest.mark.parametrize("departure", [
    {"decay_per_head": True}, {"short_conv": False}, {"mla_rotated": True},
    {"gate": "silu"}])
def test_the_reference_tells_each_departure_apart(departure):
    """What the benchmark's check must refuse: against the reference
    with one decay a head, without the short convolution, with RoPE in
    the MLA layer or with SiLU for the output gate's sigmoid (forced to
    the system's selection, so only the arithmetic differs) the
    system's Q-values are far outside rounding."""
    cfg = tiny(shards=1, balanced=True)
    net, params = net_and_params(cfg)
    items, w = batch(cfg), jnp.ones(B)
    _, aux = jax.jit(system_loss(cfg, net))(params, params, items, w)
    sizes = mapper.sizes(cfg.network.kimi_linear, net.router_trains)

    def q_of(sz):
        (_, raux), _ = jax.jit(lambda p: reference_loss(
            cfg, net, p, p, items, w, sizes=sz,
            forced_online=aux["topk_online"],
            forced_target=aux["topk_target"]))(params)
        return np.asarray(raux["q"])

    np.testing.assert_allclose(aux["q"], q_of(sizes), atol=1e-5)
    off = np.abs(np.asarray(aux["q"]) - q_of(sizes._replace(**departure)))
    assert np.quantile(off, 0.95) > 10 * 1e-5


def test_kda_chunks_reads_layers_times_positions_over_the_chunk():
    """The family's counter: chunks the scan walked in the online net's
    forward pass, prefix (12 positions: 2 chunks of 8) and trained
    steps (20: 3), three KDA layers; and the state's RMS is a live
    number."""
    cfg = tiny()
    net, params = net_and_params(cfg)
    items, w = batch(cfg), jnp.ones(B)
    _, aux = jax.jit(system_loss(cfg, net))(params, params, items, w)
    assert net.num_kda_layers == 3
    assert float(aux["kda_chunks"]) == 3 * (-(-BURN // CHUNK)
                                            + -(-(L - BURN) // CHUNK))
    assert 0.0 < float(aux["kda_state_rms_last"]) < 1.0
    family = learner_family(cfg, net)
    assert family.metric_keys[-2:] == ("kda_chunks", "kda_state_rms_last")
    # the nets without a scan layer keep their counters
    glm = get_config("glm_tiny_q")
    keys = learner_family(glm, build_network(glm.network, None)).metric_keys
    assert "kda_chunks" not in keys


def test_a_blocks_recomputation_keeps_the_selection():
    from jax._src.ad_checkpoint import saved_residuals

    cfg = tiny()
    net, params = net_and_params(cfg)
    tokens = jnp.asarray(batch(cfg)["obs"])
    saved = saved_residuals(
        lambda p: net.apply(p, tokens, ())[0].sum(), params)
    kept = [a for a, why in saved if SELECTION in why]
    assert len(kept) == 3            # the three expert layers
    assert all(str(a.dtype) == "int32" for a in kept)
    # nothing of [T, d, d] a head is kept: a block saves its input only
    assert not [a for a, _ in saved if a.ndim >= 4 and L in a.shape
                and a.shape[-2:] == (8, 8)]


def test_family_rows():
    cfg = tiny()
    assert family_of(cfg) == "decoder_q"
    assert ACTOR_STATE["decoder_q"].stored == ()
    assert stored_state_spec("decoder_q", cfg) == {}
    net = build_network(cfg.network, make_env(cfg.env).spec)
    assert type(net) is KimiLinearQNet is DECODER_NETS["kimi_linear_q"]
    assert net.num_actions == 32
    name, block = decoder_block(cfg.network)
    assert name == "kimi_linear" and block is cfg.network.kimi_linear
    with pytest.raises(NotImplementedError, match="without rotation"):
        KimiLinearQNet(dataclasses.replace(cfg.network.kimi_linear,
                                           mla_use_nope=False))
    with pytest.raises(NotImplementedError, match="group stage"):
        KimiLinearQNet(dataclasses.replace(cfg.network.kimi_linear,
                                           num_expert_group=2))
    # the vocabulary's rows may go fewer ways than the experts
    kl = dataclasses.replace(cfg.network.kimi_linear, shard_count=8,
                             vocab_shard_count=2)
    assert (KimiLinearQNet(kl).num_actions,
            KimiLinearQNet(kl).experts_held) == (32, 1)


def test_env_and_family_must_agree_on_the_vocabulary():
    from ape_x_dqn_tpu.runtime.driver import ApexDriver

    cfg = tiny()
    cfg = cfg.replace(env=dataclasses.replace(cfg.env, num_tokens=64))
    with pytest.raises(ValueError, match="network.kimi_linear.vocab_size"):
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
        assert type(driver.net) is KimiLinearQNet
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
                    "moe_load_max_over_mean", "moe_compact_share",
                    "kda_chunks", "kda_state_rms_last"):
            assert np.isfinite(float(m[key])), key
        # the module's chunk of 32: one chunk a pass and KDA layer
        assert float(m["kda_chunks"]) == 3 * 2
        after = jax.device_get(state.params["layers"][1])
        for name in ("q_proj", "k_conv1d", "A_log", "dt_bias", "f_b_proj",
                     "b_proj", "g_a_proj", "o_norm", "o_proj"):
            assert not np.array_equal(before[name], after[name]), name
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
    argv = ["--config", "kimi_linear_tiny_q", "--actors", "2",
            "--max-grad-steps", "8", "--wall-clock-limit", "120",
            "--metrics-file", str(out), "--set", "eval_episodes=1",
            "--set", "eval_max_frames=100", "--set", "eval_every_steps=0"]
    assert train.main(argv) == 0
    assert os.path.getsize(out) > 0


def test_hbm_budget_admits_the_share_and_refuses_the_whole_model():
    from ape_x_dqn_tpu.runtime.train import apply_overrides
    from ape_x_dqn_tpu.utils import hbm

    v5e = int(15.75 * 1024 ** 3)

    def check(cfg):
        net = build_network(cfg.network, None)
        return hbm.check_hbm_fits(
            cfg, (), np.int32, param_count=net.param_count(),
            hbm_bytes=v5e, **hbm_price(cfg, net))

    whole = get_config("kimi_linear_48b_q")
    assert build_network(whole.network, None).param_count() > 48e9
    with pytest.raises(ValueError, match="GiB per device"):
        check(whole)
    with open(CONFIG_FILE) as fh:
        overrides = json.load(fh)["overrides"]
    share = apply_overrides(whole, overrides)
    check(share)
    # a KDA layer's state does not grow with the prefix, an MLA layer's
    # does: what the price adds for the burn-in
    net = build_network(share.network, None)
    short, long = (net.sequence_state_bytes(1, n) for n in (128, 2048))
    assert long - short == (2048 - 128) * 2 * (512 + 64)
    assert short > 4 * 4 * 32 * 128 * 128
