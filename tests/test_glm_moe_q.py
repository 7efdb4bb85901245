"""The decoder family (network.kind "glm_moe_q", runtime family
"decoder_q") at tiny widths on the CPU: hidden 64, 8 experts in 2
shards, a vocabulary of 64 in 2 slices. The net against
benchmarks/reference/glm_moe_q.py (Q, loss, gradients); the shares add
up; the latent cache equals one causal pass; token items survive the
replay byte for byte; the family's rows in runtime/family.py build
through ApexDriver; a run with actors completes; the HBM budget admits
the chip's share and refuses the whole model."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.configs import get_config
from ape_x_dqn_tpu.envs import make_env
from ape_x_dqn_tpu.models import build_network
from ape_x_dqn_tpu.runtime.family import (
    ACTOR_STATE, family_of, learner_family, server_apply_fn,
    stored_state_spec, warmup_example)
from benchmarks.harness import glm_params
from benchmarks.reference import glm_moe_q as ref

L, BURN, B = 16, 4, 3


def tiny(shards: int = 2, index: int = 0, dtype: str = "float32",
         balanced: bool = False):
    cfg = get_config("glm_tiny_q")
    glm = dataclasses.replace(cfg.network.glm, shard_count=shards,
                              shard_index=index,
                              force_balanced_routing=balanced)
    return cfg.replace(
        network=dataclasses.replace(cfg.network, glm=glm,
                                    compute_dtype=dtype),
        env=dataclasses.replace(cfg.env,
                                num_tokens=glm.vocab_size // shards))


def net_and_params(cfg, seed: int = 0):
    net = build_network(cfg.network, make_env(cfg.env).spec)
    return net, net.init(jax.random.PRNGKey(seed))


def batch(cfg, seed: int = 1) -> dict:
    rng = np.random.default_rng(seed)
    v = cfg.env.num_tokens
    mask = np.ones((B, L), np.float32)
    mask[1, 11:] = 0.0                      # an episode's tail
    terminals = np.zeros((B, L), np.float32)
    terminals[1, 10] = 1.0
    terminals[2, 7] = 1.0                   # a terminal mid-sequence
    return {"obs": rng.integers(0, v, (B, L)).astype(np.int32),
            "actions": rng.integers(0, v, (B, L)).astype(np.int32),
            "rewards": (rng.integers(0, 4, (B, L)) == 0).astype(np.float32),
            "terminals": terminals, "mask": mask}


def system_loss(cfg, net):
    family = learner_family(cfg, net)
    return family, lambda p, tp, items, w: family.loss_fn(
        p, tp, family.make_batch(items), w)


def reference_loss(cfg, params, target, items, w, **kw):
    return ref.loss_and_gradients(
        glm_params.reference_params(params),
        glm_params.reference_params(target), items["obs"],
        items["actions"], items["rewards"], items["terminals"],
        items["mask"], w, sizes=glm_params.sizes(cfg.network.glm),
        burn_in=cfg.replay.burn_in, n_step=cfg.learner.n_step,
        gamma=cfg.learner.gamma, eta=cfg.replay.priority_eta,
        huber_delta=cfg.learner.huber_delta, **kw)


def test_param_count_is_the_tree_and_names_are_hf():
    cfg = tiny()
    net, params = net_and_params(cfg)
    assert net.param_count() == sum(
        x.size for x in jax.tree.leaves(params))
    moe = params["layers"][1]["mlp"]
    assert moe["gate"].shape == (64, 8)          # router over ALL experts
    assert moe["experts"]["gate_proj"].shape == (4, 64, 32)   # 4 held
    assert params["embed_tokens"].shape == (32, 64)
    assert params["lm_head"].shape == (64, 32)
    assert "experts" not in params["layers"][0]["mlp"]   # leading dense


def test_published_parameter_count_of_the_chip_share():
    """1 dense + 4 expert layers, 8 of 64 experts, 19,360 rows: the
    arithmetic of ISSUE 30 (591.3 M), from shapes alone."""
    from ape_x_dqn_tpu.runtime.train import apply_overrides

    cfg = apply_overrides(get_config("glm47_flash_q"), [
        "network.glm.num_hidden_layers=5", "network.glm.shard_count=8"])
    net = build_network(cfg.network, None)
    assert net.param_count() == 591_294_976
    assert net.num_actions == 19_360 and net.experts_held == 8


@pytest.mark.parametrize("shards,index,balanced", [
    (1, 0, False), (2, 0, False), (2, 1, False), (2, 0, True), (2, 1, True)])
def test_net_matches_reference_float32(shards, index, balanced):
    cfg = tiny(shards, index, balanced=balanced)
    net, params = net_and_params(cfg)
    tokens = jnp.asarray(batch(cfg)["obs"])
    q, _, stats = net.apply_with_stats(params, tokens, ())
    want, own, _ = ref.forward(glm_params.reference_params(params), tokens,
                               glm_params.sizes(cfg.network.glm))
    np.testing.assert_allclose(q, want, atol=1e-5)
    assert (np.sort(stats["topk"], -1) == np.sort(own, -1)).all()
    first = index * net.experts_held
    here = (own >= first) & (own < first + net.experts_held)
    assert int(stats["expert_rows"].sum()) == int(here.sum())


@pytest.mark.parametrize("balanced", [False, True])
def test_loss_and_gradients_match_reference_float32(balanced):
    cfg = tiny(balanced=balanced)
    net, params = net_and_params(cfg)
    target = net.init(jax.random.PRNGKey(7))
    items, w = batch(cfg), np.asarray([1.0, 0.5, 0.8], np.float32)
    family, loss_fn = system_loss(cfg, net)
    (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, target, items, w)
    (want, want_aux), want_grads = reference_loss(cfg, params, target,
                                                  items, w)
    assert float(loss) == pytest.approx(float(want), abs=1e-5)
    np.testing.assert_allclose(aux["td_abs"], want_aux["priorities"],
                               atol=1e-5)
    assert float(aux["valid_frac"]) == pytest.approx(
        float(want_aux["valid"].mean()), abs=1e-6)
    back = glm_params.system_gradients(want_grads)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), exp in zip(flat, jax.tree.leaves(back)):
        np.testing.assert_allclose(got, exp, atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))
    # the buffers and, in a share, the router: no gradient at all
    moe = grads["layers"][1]["mlp"]
    assert not np.any(moe["e_score_correction_bias"])
    assert not np.any(moe["gate"])
    assert np.any(moe["experts"]["down_proj"])
    # the family's counters ride the aux
    assert set(family.metric_keys) <= set(aux)
    assert 0 < float(aux["moe_rows_grad"]) < float(aux["moe_rows"])


def big_batch(cfg, count: int, length: int = L, seed: int = 3) -> dict:
    """`count` whole sequences: enough tokens for a half share's
    capacity to lie below its worst case (models/expert_layer.py)."""
    rng = np.random.default_rng(seed)
    v = cfg.env.num_tokens
    shape = (count, length)
    return {"obs": rng.integers(0, v, shape).astype(np.int32),
            "actions": rng.integers(0, v, shape).astype(np.int32),
            "rewards": (rng.integers(0, 4, shape) == 0).astype(np.float32),
            "terminals": np.zeros(shape, np.float32),
            "mask": np.ones(shape, np.float32)}


def selecting_only_held(params: dict, net) -> dict:
    """The same parameters with a selection bias under which every
    token's top-k are experts this share holds."""
    first, held = net.share.first, net.share.held
    ids = np.arange(net.share.experts)
    b = jnp.asarray(np.where((ids >= first) & (ids < first + held),
                             10.0, -10.0), jnp.float32)
    layers = [
        {**p, "mlp": {**p["mlp"], "e_score_correction_bias": b}}
        if "experts" in p["mlp"] else p for p in params["layers"]]
    return {**params, "layers": layers}


@pytest.mark.parametrize("selection", ["forced", "own"])
def test_compact_share_says_which_steps_paid_the_full_width(selection):
    """32 sequences through a half share: the trained segment's 768
    assignments have buffers of 640 rows. Under the forced selection
    half of them land here and every application fits (1.0); under a
    selection that sends every token here the trained segments overflow
    and take the full width (0.5: the prefix passes' capacity IS their
    full width) - with the loss and the priorities the reference's
    either way."""
    from ape_x_dqn_tpu.models.expert_layer import capacity

    count = 32
    cfg = tiny(balanced=selection == "forced")
    net, params = net_and_params(cfg)
    target = net.init(jax.random.PRNGKey(7))
    if selection == "own":
        params = selecting_only_held(params, net)
        target = selecting_only_held(target, net)
    trained, prefix = count * (L - BURN), count * BURN
    k = net.share.top_k
    assert capacity(net.share, trained) < k * trained
    assert capacity(net.share, prefix) == k * prefix
    items, w = big_batch(cfg, count), np.ones(count, np.float32)
    family, loss_fn = system_loss(cfg, net)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        loss_fn, has_aux=True))(params, target, items, w)
    (want, want_aux), want_grads = reference_loss(cfg, params, target,
                                                  items, w)
    assert float(loss) == pytest.approx(float(want), abs=1e-5)
    np.testing.assert_allclose(aux["td_abs"], want_aux["priorities"],
                               atol=1e-5)
    back = glm_params.system_gradients(want_grads)
    for got, exp in zip(jax.tree.leaves(grads), jax.tree.leaves(back)):
        np.testing.assert_allclose(got, exp, atol=1e-5)
    share = float(aux["moe_compact_share"])
    if selection == "forced":
        assert share == 1.0
    else:
        layers = sum("experts" in p["mlp"] for p in params["layers"])
        assert float(aux["moe_rows"]) == layers * 2 * k * count * L
        assert share == 0.5


def test_router_trains_when_the_layer_is_whole():
    cfg = tiny(shards=1)
    net, params = net_and_params(cfg)
    items, w = batch(cfg), np.ones(B, np.float32)
    _, loss_fn = system_loss(cfg, net)
    grads = jax.grad(lambda p: loss_fn(p, params, items, w)[0])(params)
    (_, _), want = reference_loss(cfg, params, params, items, w)
    got = grads["layers"][1]["mlp"]["gate"]
    assert np.any(got)
    np.testing.assert_allclose(got, want["layers"][1]["router"], atol=1e-5)


def test_router_is_held_fixed_only_while_the_exchange_is_missing():
    """The share's stop-gradient and the mesh's expert axis may not
    exist together: `build_network` asks parallel/mesh.py, so the PR
    that adds the axis and the all-to-all un-freezes the router; and a
    share told that the exchange exists trains it."""
    from ape_x_dqn_tpu.models.glm_moe_q import GlmMoeQNet
    from ape_x_dqn_tpu.parallel import mesh

    cfg = tiny(shards=2)
    net, params = net_and_params(cfg)
    assert mesh.has_expert_exchange() == (
        mesh.EXPERT_AXIS in mesh.AXIS_NAMES)
    assert net.router_trains == mesh.has_expert_exchange()
    items, w = batch(cfg), np.ones(B, np.float32)
    exchanged = GlmMoeQNet(cfg.network.glm, cfg.network.compute_dtype,
                           expert_exchange=True)
    assert exchanged.router_trains
    _, loss_fn = system_loss(cfg, exchanged)
    grads = jax.grad(lambda p: loss_fn(p, params, items, w)[0])(params)
    assert np.any(grads["layers"][1]["mlp"]["gate"])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_forced_balanced_routing_is_the_same_work_whatever_the_weights(seed):
    """What the option is for: the rows routed to the experts held here
    do not depend on the weights (here: their seed, and a net whose
    every weight has moved), only on the tokens; over many tokens they
    are the share's expectation, k x held / total of them; and the
    selection follows a token through the latent cache (positions run
    on), so prefix-then-segment selects as one pass does."""
    cfg = tiny(balanced=True)
    net, params = net_and_params(cfg, seed)
    glm = cfg.network.glm
    tokens = jnp.asarray(np.random.default_rng(5).integers(
        0, cfg.env.num_tokens, (8, 64)).astype(np.int32))
    _, _, stats = net.apply_with_stats(params, tokens, ())
    _, _, first = net.apply_with_stats(net_and_params(cfg, 99)[1], tokens, ())
    moved = jax.tree.map(lambda x: x + 0.05, params)
    _, _, after = net.apply_with_stats(moved, tokens, ())
    for other in (first, after):
        assert (stats["topk"] == other["topk"]).all()
        assert (stats["expert_rows"] == other["expert_rows"]).all()
    n, k = tokens.size, glm.num_experts_per_tok
    # tokens differ in what they select: most of the C(8, 2) = 28 pairs
    assert len(set(map(tuple, np.sort(stats["topk"], -1).reshape(-1, k)))
               ) > 20
    assert (np.diff(np.sort(stats["topk"], -1), axis=-1) > 0).all()
    expected = n * k * net.experts_held / glm.n_routed_experts
    per_layer = stats["expert_rows"].sum(axis=1)
    assert np.all(np.abs(per_layer - expected) <= 0.1 * expected)
    _, state = net.apply(params, tokens[:, :24], ())
    _, _, tail = net.apply_with_stats(params, tokens[:, 24:], state)
    assert (tail["topk"] == stats["topk"][:, :, 24:]).all()
    # and without the option the same two nets select differently
    plain = tiny()
    a = net_and_params(plain, seed)[0].apply_with_stats(
        net_and_params(plain, seed)[1], tokens, ())[2]["topk"]
    b = net_and_params(plain, 99)[0].apply_with_stats(
        net_and_params(plain, 99)[1], tokens, ())[2]["topk"]
    assert (np.sort(a, -1) != np.sort(b, -1)).any()


def test_recomputation_keeps_the_selection():
    """A block's backward pass is recomputed from its input, except the
    experts selected: re-deciding a near-tie in recomputed bfloat16
    activations sent a token's gradient to an expert the forward pass
    never used (glm_moe_q's docstring). The residuals say so: per
    expert layer one int32 [tokens, k] under the selection's name."""
    from jax._src.ad_checkpoint import saved_residuals

    from ape_x_dqn_tpu.models.glm_moe_q import SELECTION

    cfg = tiny(dtype="bfloat16")
    net, params = net_and_params(cfg)
    tokens = jnp.asarray(batch(cfg)["obs"])
    kept = saved_residuals(
        lambda p: net.apply(p, tokens, ())[0].sum(), params)
    named = [aval for aval, why in kept if SELECTION in why]
    k = cfg.network.glm.num_experts_per_tok
    assert len(named) == net.num_moe_layers == 2
    assert all(a.shape == (B * L, k) and a.dtype == jnp.int32
               for a in named)


def test_bfloat16_stays_in_a_stated_band():
    """bfloat16 compute against the float32 reference forced to the
    system's routing: the 95th percentile of the Q error within 3% of
    the mean |Q| (measured here: 0.6-1.2%), the loss within 2%."""
    cfg = tiny(dtype="bfloat16")
    net, params = net_and_params(cfg)
    items, w = batch(cfg), np.ones(B, np.float32)
    tokens = jnp.asarray(items["obs"])
    q, _, stats = net.apply_with_stats(params, tokens, ())
    assert q.dtype == jnp.float32
    want, _, _ = ref.forward(glm_params.reference_params(params), tokens,
                             glm_params.sizes(cfg.network.glm),
                             forced_topk=stats["topk"])
    err = np.quantile(np.abs(np.asarray(q) - want), 0.95)
    assert err <= 0.03 * np.abs(want).mean()
    _, loss_fn = system_loss(cfg, net)
    loss, _ = loss_fn(params, params, items, w)
    (ref_loss, _), _ = reference_loss(
        cfg, params, params, items, w, forced_online=stats["topk"],
        forced_target=stats["topk"])
    assert float(loss) == pytest.approx(float(ref_loss), rel=0.02)


def test_the_shares_add_up():
    """One expert layer's output from every shard, what each computes
    alike (the shared expert) counted once, sums to the uncut
    reference's layer output."""
    whole = tiny(shards=1)
    net1, params1 = net_and_params(whole)
    layer = params1["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(3), (B, L, 64), jnp.float32)
    want, _, _ = ref.expert_layer(
        glm_params.reference_params(params1)["layers"][1], x,
        glm_params.sizes(whole.network.glm), None, lambda a: a)
    shared = ref.swiglu(x, glm_params.reference_params(
        params1)["layers"][1]["shared"], lambda a: a)
    total = jnp.zeros_like(want)
    rows = 0
    for index in range(2):
        cfg = tiny(shards=2, index=index)
        net, _ = net_and_params(cfg)
        held = net.experts_held
        mlp = dict(layer["mlp"])
        mlp["experts"] = {k: v[index * held:(index + 1) * held]
                          for k, v in layer["mlp"]["experts"].items()}
        out, n, _ = net._moe(mlp, x, jnp.float32)
        total = total + (out - shared)
        rows += int(n.sum())
    np.testing.assert_allclose(total + shared, want, atol=1e-5)
    assert rows == B * L * whole.network.glm.num_experts_per_tok


def test_prefix_then_segment_through_the_cache_equals_one_pass():
    """Values: the trained steps through the latent cache the burn-in
    left equal the same steps of one causal pass. Gradients: the
    family's loss (prefix, stop-gradient, segment) equals the
    reference's one pass with the gradient stopped at the burn-in's
    keys and values — test_loss_and_gradients_match_reference_float32
    holds that; here the cache's own shape and content."""
    cfg = tiny()
    net, params = net_and_params(cfg)
    tokens = jnp.asarray(batch(cfg)["obs"])
    full, state_full = net.apply(params, tokens, ())
    head, state = net.apply(params, tokens[:, :BURN], ())
    tail, state_tail = net.apply(params, tokens[:, BURN:], state)
    np.testing.assert_allclose(head, full[:, :BURN], atol=1e-6)
    np.testing.assert_allclose(tail, full[:, BURN:], atol=1e-6)
    glm = cfg.network.glm
    assert len(state) == glm.num_hidden_layers
    assert state[0][0].shape == (B, BURN, glm.kv_lora_rank)
    assert state[0][1].shape == (B, BURN, glm.qk_rope_head_dim)
    for a, b in zip(jax.tree.leaves(state_tail),
                    jax.tree.leaves(state_full)):
        np.testing.assert_allclose(a, b, atol=1e-6)
    # without the stop-gradient the two differ: the cut is real
    _, loss_fn = system_loss(cfg, net)
    items, w = batch(cfg), np.ones(B, np.float32)
    cut = jax.grad(lambda p: loss_fn(p, params, items, w)[0])(params)
    (_, _), uncut = ref.loss_and_gradients(
        glm_params.reference_params(params),
        glm_params.reference_params(params), items["obs"],
        items["actions"], items["rewards"], items["terminals"],
        items["mask"], w, sizes=glm_params.sizes(glm), burn_in=0,
        n_step=cfg.learner.n_step, gamma=cfg.learner.gamma,
        eta=cfg.replay.priority_eta)
    assert not np.allclose(cut["layers"][0]["kv_a_proj_with_mqa"],
                           uncut["layers"][0]["wkv_a"], atol=1e-5)


def test_token_items_survive_add_and_sample_byte_for_byte():
    from ape_x_dqn_tpu.replay.prioritized import PrioritizedReplay
    from ape_x_dqn_tpu.replay.sequence import sequence_item_spec

    cfg = tiny()
    spec = make_env(cfg.env).spec
    assert spec.obs_shape == () and spec.obs_dtype == np.int32
    assert stored_state_spec("decoder_q", cfg) == {}
    item_spec = sequence_item_spec(spec.obs_shape, spec.obs_dtype, L,
                                   stored_state_spec("decoder_q", cfg))
    # obs is int32 [L]; no state entry, no zero-width leaf
    assert set(item_spec) == {"obs", "actions", "rewards", "terminals",
                              "mask"}
    assert item_spec["obs"].shape == (L,)
    assert item_spec["obs"].dtype == np.int32
    replay = PrioritizedReplay(capacity=8, alpha=0.6, beta=0.4, eps=1e-6)
    state = replay.init(item_spec)
    rng = np.random.default_rng(0)
    items = {k: rng.integers(0, 32, (8, L)).astype(v.dtype)
             for k, v in item_spec.items()}
    state = replay.add(state, items, jnp.ones(8))
    got, idx, _ = replay.sample_items(state, jax.random.PRNGKey(0), 8)
    for k in items:
        np.testing.assert_array_equal(got[k], items[k][np.asarray(idx)])
        assert got[k].dtype == items[k].dtype


def test_family_rows_and_server_protocol():
    cfg = tiny()
    assert family_of(cfg) == "decoder_q"
    spec = make_env(cfg.env).spec
    example = warmup_example("decoder_q", cfg, spec)
    assert set(example) == {"obs", "ctx", "n"}
    assert example["ctx"].shape == (cfg.replay.seq_length,)
    assert ACTOR_STATE["decoder_q"].stored == ()
    assert ACTOR_STATE["r2d2"].stored == ("c", "h")
    net, params = net_and_params(cfg)
    apply = jax.jit(server_apply_fn("decoder_q", net))
    ids = np.asarray([5, 9, 2, 7, 1], np.int32)
    state = {k: v[None] for k, v in ACTOR_STATE["decoder_q"].zeros(
        cfg).items()}
    qs = []
    for t in ids:
        out = apply(params, {"obs": np.asarray([t], np.int32), **state})
        state = {"ctx": out["ctx"], "n": out["n"]}
        qs.append(np.asarray(out["q"][0]))
    assert int(state["n"][0]) == 5
    assert list(np.asarray(state["ctx"][0][:5])) == list(ids)
    # stateless windows answer what one causal pass answers
    full, _ = net.apply(params, ids[None], ())
    np.testing.assert_allclose(np.stack(qs), full[0], atol=1e-5)
    # a full window drops its oldest id
    full_ctx = {"ctx": np.arange(L, dtype=np.int32)[None],
                "n": np.asarray([L], np.int32)}
    out = apply(params, {"obs": np.asarray([31], np.int32), **full_ctx})
    assert list(np.asarray(out["ctx"][0])) == list(range(1, L)) + [31]
    assert int(out["n"][0]) == L


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
        assert tuple(driver._item_spec) == (
            "obs", "actions", "rewards", "terminals", "mask")
        state = driver.state
        rng = np.random.default_rng(0)
        n = 16
        items = {"obs": rng.integers(0, 32, (n, L)).astype(np.int32),
                 "actions": rng.integers(0, 32, (n, L)).astype(np.int32),
                 "rewards": rng.normal(size=(n, L)).astype(np.float32),
                 "terminals": np.zeros((n, L), np.float32),
                 "mask": np.ones((n, L), np.float32)}
        state = driver.learner.add(state, items, jnp.ones(n))
        before = jax.device_get(state.params["layers"][1]["mlp"])
        state, m = driver.learner.train_many(state, 2)
        assert int(state.step) == 2 and np.isfinite(float(m["loss"]))
        for key in ("valid_frac", "moe_rows", "moe_rows_grad",
                    "moe_load_max_over_mean", "moe_compact_share"):
            assert np.isfinite(float(m[key])), key
        after = jax.device_get(state.params["layers"][1]["mlp"])
        # Adam moved the experts; the selection bias and, in a share,
        # the router stayed where the seed put them
        assert not np.array_equal(before["experts"]["up_proj"],
                                  after["experts"]["up_proj"])
        np.testing.assert_array_equal(before["e_score_correction_bias"],
                                      after["e_score_correction_bias"])
        np.testing.assert_array_equal(before["gate"], after["gate"])
    finally:
        driver.server.stop()


def test_train_run_with_actors_completes(tmp_path):
    from ape_x_dqn_tpu.runtime import train

    out = tmp_path / "m.jsonl"
    argv = ["--config", "glm_tiny_q", "--actors", "2",
            "--max-grad-steps", "8", "--wall-clock-limit", "120",
            "--metrics-file", str(out), "--set", "eval_episodes=1",
            "--set", "eval_max_frames=100", "--set", "eval_every_steps=0"]
    assert train.main(argv) == 0
    assert os.path.getsize(out) > 0


def test_hbm_budget_admits_the_share_and_refuses_the_whole_model():
    """The family prices its own step (`family.hbm_price`: the net's
    `step_transient_bytes`, no stored state); utils/hbm.py knows no
    family."""
    from ape_x_dqn_tpu.runtime.family import hbm_price
    from ape_x_dqn_tpu.runtime.train import apply_overrides
    from ape_x_dqn_tpu.utils import hbm

    v5e = int(15.75 * 1024 ** 3)

    def check(cfg):
        net = build_network(cfg.network, None)
        return hbm.check_hbm_fits(
            cfg, (), np.int32, param_count=net.param_count(),
            hbm_bytes=v5e, **hbm_price(cfg, net))

    whole = get_config("glm47_flash_q")
    assert build_network(whole.network, None).param_count() > 25e9
    with pytest.raises(ValueError, match="GiB per device"):
        check(whole)
    share = apply_overrides(whole, [
        "network.glm.num_hidden_layers=5", "network.glm.shard_count=8",
        "env.num_tokens=19360"])
    budget = check(share)
    gib = 1024 ** 3
    assert budget.model_state == 16 * 591_294_976
    # 65,536 sequences x (5 x 512 x 4 B): no state entry is priced
    assert budget.replay_storage == 65_536 * 10_240
    # 4 B a parameter + three [16, 384, 19,360] float32 + 1 GiB
    assert budget.headroom == (4 * 591_294_976
                               + 3 * 16 * 384 * 19_360 * 4 + gib)
    assert 12.0 < budget.total / gib < 15.75
    # a fifth expert layer does not fit
    with pytest.raises(ValueError, match="GiB per device"):
        check(apply_overrides(share, ["network.glm.num_hidden_layers=6"]))


def test_hbm_price_of_the_other_families():
    """r2d2 prices its stored (c, h) through the family, to the byte
    what utils/hbm.py took by default before; a family without
    sequence state or a step price hands over nothing."""
    from ape_x_dqn_tpu.runtime.family import hbm_price
    from ape_x_dqn_tpu.utils import hbm

    r2d2 = get_config("r2d2")
    price = hbm_price(r2d2, object())
    assert price == {"stored_state_floats": 2 * r2d2.network.lstm_size}
    assert hbm.run_budget(r2d2, (84, 84, 4), np.uint8, **price) == (
        hbm.run_budget(r2d2, (84, 84, 4), np.uint8))
    assert hbm_price(get_config("pong"), object()) == {}


def test_env_and_family_must_agree_on_the_vocabulary():
    from ape_x_dqn_tpu.runtime.driver import ApexDriver

    cfg = tiny()
    cfg = cfg.replace(env=dataclasses.replace(cfg.env, num_tokens=64))
    with pytest.raises(ValueError, match="env.num_tokens=32"):
        ApexDriver(cfg)


def test_synthetic_token_env():
    env = make_env(get_config("glm_tiny_q").env, seed=3)
    obs = env.reset()
    assert obs.dtype == np.int32 and obs.shape == ()
    steps, done, rewards = 0, False, []
    while not done:
        obs, r, done, info = env.step(int(obs) % 7)
        rewards.append(r)
        steps += 1
        assert 0 <= int(obs) < 64
    assert 24 <= steps <= 4096 and info["terminal"]
    assert set(rewards) <= {-1.0, 0.0, 1.0}
    assert sum(r != 0 for r in rewards) == steps // 16
