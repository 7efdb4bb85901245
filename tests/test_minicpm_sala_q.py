"""The decoder family's seventh net (network.kind "minicpm_sala_q") at
tiny widths on the CPU: hidden 64, 4 heads of 16 over 1 key-value head,
sparse / lightning / lightning / sparse, key blocks of 8, compressed
keys over 4 positions every 2, a local window of 16, 6 blocks attended,
dense up to 32 positions - so that a history of 100 tokens is dense,
then crosses `sparse_dense_len`, then selects. Both ops against their
one-position definitions (the chunked lightning form = the recurrence;
the pool's write / compress / select / attend = the reference's plain
per-query form); the net against benchmarks/reference/minicpm_sala_q.py,
the full forward pass and prefill + decode through the slot state; the
departures the reference can make are seen; the scopes are in the
lowered `extend`; the family's rows; the family's loss trains it; the
HBM budget prices the slot state from what the net says."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.configs import get_config
from ape_x_dqn_tpu.models import DECODER_NETS, build_network, decoder_block
from ape_x_dqn_tpu.models.minicpm_sala_q import MiniCpmSalaQNet
from ape_x_dqn_tpu.ops import block_select_attention as bsa
from ape_x_dqn_tpu.ops import lightning_attention as la
from ape_x_dqn_tpu.runtime import family as fam
from benchmarks.harness import minicpm_sala_params as mapper
from benchmarks.reference import minicpm_sala_q as ref

T = 100
DENSE = 32


def tiny():
    return get_config("minicpm_sala_tiny_q")


@pytest.fixture(scope="module")
def built():
    """The tiny net, its parameters with matrices 8x the seed's (at 0.02
    a mixer is a thousandth of the stream and no departure in it is
    seen), one 100-token history, the net's full pass and the
    reference's."""
    cfg = tiny()
    net = build_network(cfg.network, None)
    params = jax.tree.map(lambda w: w * 8 if w.ndim == 2 else w,
                          net.init(jax.random.PRNGKey(0)))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, T), 0, 64)
    q, _, stats = jax.jit(net.apply_with_stats)(params, tokens)
    sizes = mapper.sizes(cfg.network.minicpm_sala)
    want, own, _ = ref.forward(mapper.reference_params(params), tokens[0],
                               sizes)
    return dict(cfg=cfg, net=net, params=params, tokens=tokens, q=q,
                sel=np.asarray(stats["sel"]), sizes=sizes,
                want=np.asarray(want), own=np.asarray(own))


# -- the ops against their one-position definitions ---------------------------


@pytest.mark.parametrize("chunk, lengths", [(1, (37, 37)), (7, (37, 37)),
                                            (16, (37, 20))])
def test_chunked_lightning_is_the_recurrence(chunk, lengths):
    b, t, h, d = 2, 37, 4, 16
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v = (jax.random.normal(x, (b, t, h, d)) for x in keys[:3])
    start = jax.random.normal(keys[3], (b, h, d, d))
    slope = la.slopes(h)
    valid = jnp.arange(t)[None, :] < jnp.asarray(lengths)[:, None]
    state, rows = start, []
    for i in range(t):
        o, after = la.step(q[:, i], k[:, i], v[:, i], state, slope, 0.25)
        state = jnp.where(valid[:, i, None, None, None], after, state)
        rows.append(o)
    o, end = la.chunked(q, k, v, start, slope, 0.25, valid, chunk=chunk)
    np.testing.assert_allclose(np.asarray(o)[np.asarray(valid)],
                               np.stack(rows, 1)[np.asarray(valid)],
                               atol=2e-5)
    np.testing.assert_allclose(end, state, atol=2e-5)


def _pooled(q, k, v, sz, base_blocks, chunk):
    """q [T, H, d], k, v [T, G, d] through the pool's entry points as
    the net calls them, `chunk` positions a call (1: the gathered
    decode form) -> (o [T, H, d], sel [T, G, topk])."""
    t, heads, d = q.shape
    g = k.shape[1]
    blocks = -(-t // sz.block)
    positions = (base_blocks + blocks + blocks) * sz.block
    kpool = jnp.zeros((g, positions, d))
    vpool, ck = kpool, jnp.zeros((g, positions // sz.stride, d))
    base = jnp.asarray([base_blocks * sz.block])
    q = q.reshape(t, g, heads // g, d)
    outs, sels = [], []
    for lo in range(0, t, chunk):
        n = min(chunk, t - lo)
        at = jnp.arange(lo, lo + n)
        valid = jnp.ones((1, n), bool)
        kpool = bsa.write(kpool, k[None, lo:lo + n], base[:, None] + at,
                          valid)
        vpool = bsa.write(vpool, v[None, lo:lo + n], base[:, None] + at,
                          valid)
        ck = bsa.compress(ck, kpool, base, jnp.asarray([lo]),
                          jnp.asarray([lo + n]), n, sz)
        own = jax.lax.dynamic_slice_in_dim(
            ck, base_blocks * sz.per, blocks * sz.per, 1)
        sel = bsa.select(q[lo:lo + n], own, at, sz, blocks)
        if chunk == 1:
            every = bsa.dense_blocks(at, max(blocks, sz.topk), sz)
            listed = jnp.where(
                at[0] < sz.dense_len, jnp.broadcast_to(
                    every[:, None], (1, g, every.shape[-1])),
                jnp.pad(sel, ((0, 0), (0, 0),
                              (0, every.shape[-1] - sz.topk)),
                        constant_values=-1))
            o = bsa.attend_gathered(q[lo:lo + 1], kpool, vpool, base,
                                    listed, at, sz)
        else:
            m = jnp.arange(blocks + 1)
            picked = (jnp.where(sel < 0, blocks, sel)[..., None]
                      == m).any(axis=-2)[..., :blocks]
            allowed = jnp.where((at + 1 > sz.dense_len)[:, None, None],
                                picked, True)
            o = bsa.attend_tiles(q[lo:lo + n], at, allowed, kpool, vpool,
                                 base[0], sz, blocks * sz.block)
        outs.append(o.reshape(n, heads, d))
        sels.append(sel)
    return jnp.concatenate(outs), jnp.concatenate(sels)


@pytest.mark.parametrize("t, chunk", [(24, 5), (DENSE, 1), (56, 1),
                                      (56, 56)])
def test_block_selection_is_the_plain_per_query_form(t, chunk):
    """Below, at and past `dense_len`; a decode step at a time, in
    chunks of 5 and in one."""
    sz = bsa.Sizes(8, 4, 2, 1, 16, 6, DENSE)
    keys = jax.random.split(jax.random.PRNGKey(t), 3)
    q = jax.random.normal(keys[0], (t, 4, 16))
    k, v = (jax.random.normal(x, (t, 2, 16)) for x in keys[1:])
    sizes = mapper.sizes(tiny().network.minicpm_sala)
    want, own, _ = ref.sparse_attention(q, k, v, sizes)
    got, sel = _pooled(q, k, v, sz, 3, chunk)
    np.testing.assert_allclose(got, want, atol=2e-5)
    due = np.arange(t) + 1 > DENSE
    np.testing.assert_array_equal(np.sort(np.asarray(sel)[due], -1),
                                  np.sort(np.asarray(own)[due], -1))


# -- the net against the reference -------------------------------------------


@pytest.mark.parametrize("upto", [DENSE, T])
def test_full_forward_matches_reference(built, upto):
    """Below `dense_len` alone, and across it: the same selection, Q to
    float32's rounding."""
    np.testing.assert_allclose(built["q"][0, :upto], built["want"][:upto],
                               atol=5e-6)
    sel = built["sel"][0].transpose(1, 0, 2, 3)        # [layers, T, G, k]
    np.testing.assert_array_equal(np.sort(sel[:, DENSE:upto], -1),
                                  np.sort(built["own"][:, DENSE:upto], -1))


@pytest.mark.parametrize("chunk", [1, 5, T])
def test_prefill_then_decode_through_the_slot_state(built, chunk):
    """60 tokens prefilled `chunk` at a time (a ragged last chunk among
    them), then 40 decode steps, in two slots side by side: Q at every
    answered position is the reference's full forward pass."""
    net, params, tokens = built["net"], built["params"], built["tokens"]
    extend = jax.jit(lambda p, s, i: net.extend(p, s, i, max_len=104))
    state = net.slot_state(3, 3 * 104, 104)
    slot, base = jnp.asarray([2, 0]), jnp.asarray([0, 13])
    prefill, n = 60, min(chunk, 60)
    for lo in range(0, prefill, n):
        take = min(n, prefill - lo)
        rows = jnp.pad(tokens[:, lo:lo + take], ((0, 0), (0, n - take)))
        out, state = extend(params, state, {
            "obs": rows[:, 0] if n == 1 else rows,
            **({} if n == 1 else {"n_valid": jnp.asarray([take, take])}),
            "slot": slot, "base": base,
            "fresh": jnp.asarray([lo == 0] * 2)})
        np.testing.assert_allclose(out["q"][0], built["want"][lo + take - 1],
                                   atol=5e-6)
    for t in range(prefill, T):
        out, state = extend(params, state, {
            "obs": tokens[:, t], "slot": slot, "base": base,
            "fresh": jnp.asarray([False, False])})
        np.testing.assert_allclose(out["q"], built["q"][:, t], atol=5e-6)
        np.testing.assert_allclose(out["q"][0], built["want"][t], atol=5e-6)
    np.testing.assert_array_equal(state["len"], [T, 0, T, 0])
    assert int(out["counters"]["extend_tokens"]) == 2
    # past dense_len a query attends its 6 blocks of the 13 there are
    assert int(out["counters"]["sparse_blocks_attended"]) == 2 * 2 * 6
    assert int(out["counters"]["sparse_blocks_in_context"]) == 2 * 2 * 13


@pytest.mark.parametrize("departure", [
    "dense_always", "decay_one", "forced_blocks_dropped",
    "stale_compressed"])
def test_the_reference_tells_each_departure_apart(built, departure):
    cfg = built["cfg"]
    sizes = mapper.sizes(cfg.network.minicpm_sala, **{departure: True})
    other, _, _ = ref.forward(mapper.reference_params(built["params"]),
                              built["tokens"][0], sizes)
    apart = np.abs(np.asarray(other) - built["want"])[DENSE:].max()
    assert apart > 1e-3, apart


def test_a_forced_selection_replaces_the_references_own(built):
    forced = jnp.asarray(built["sel"][0].transpose(1, 0, 2, 3))
    got, _, scores = ref.forward(
        mapper.reference_params(built["params"]), built["tokens"][0],
        built["sizes"], forced=forced, score_at=np.asarray([60, 99]))
    np.testing.assert_allclose(got, built["want"], atol=5e-6)
    assert scores.shape == (2, 2, 1, 13)


def test_the_scopes_are_in_the_lowered_extend(built):
    net, params = built["net"], built["params"]
    state = net.slot_state(2, 2 * 64, 64)
    rows = {"slot": jnp.zeros(2, jnp.int32), "base": jnp.zeros(2, jnp.int32),
            "fresh": jnp.ones(2, jnp.int32)}

    def text(inputs):
        return jax.jit(lambda p, s, i: net.extend(
            p, s, i, max_len=64)).lower(params, state, inputs).as_text(
                debug_info=True)

    decode = text({"obs": jnp.zeros(2, jnp.int32), **rows})
    chunk = text({"obs": jnp.zeros((2, 16), jnp.int32),
                  "n_valid": jnp.full(2, 16, jnp.int32), **rows})
    both = ("sala.embed", "sala.lightning/sala.lightning.proj",
            "sala.lightning/sala.lightning.state",
            "sala.lightning/sala.lightning.out",
            "sala.sparse/sala.sparse.proj", "sala.sparse/sala.sparse.compress",
            "sala.sparse/sala.sparse.out", "sala.mlp", "sala.head",
            "slots.read", "slots.write", "sala.sparse/slots.write",
            # inside a chunk's `lax.map` the name stack starts again
            "sala.sparse.select/", "sala.sparse.dense/")
    for name in both:
        assert name in decode and name in chunk, name
    assert "sala.sparse.attend/" in decode      # a branch of its `cond`
    # a chunk gathers and scatters the rows' matrices around the scan; a
    # decode step's kernel addresses the pool itself, under `.state`
    for name in ("sala.lightning/slots.read", "sala.lightning/slots.write"):
        assert name in chunk and name not in decode, name


def test_the_slot_kernel_is_the_decode_steps_alone(built, monkeypatch):
    """`la.step_slots` is traced once a lightning layer by a decode step
    and never by a prefill chunk or the learner's pass (without and with
    a burn-in state): their programs are the gather, `la.chunked` and the
    scatter they were (at PR 56 their lowered text was the parent
    commit's to the byte), and a decode step scatters no matrix."""
    net, params = built["net"], built["params"]
    calls, kernel = [], la.step_slots

    def counted(*args):
        calls.append(args[0].shape)
        return kernel(*args)

    monkeypatch.setattr(la, "step_slots", counted)
    state = net.slot_state(2, 2 * 64, 64)
    rows = {"slot": jnp.zeros(2, jnp.int32), "base": jnp.zeros(2, jnp.int32),
            "fresh": jnp.ones(2, jnp.int32)}

    def extend_text(inputs):
        return jax.jit(lambda p, s, i: net.extend(
            p, s, i, max_len=64)).lower(params, state, inputs).as_text()

    def pool_scatters(text):      # whole [H, d, d] matrices by slot
        return sum("stablehlo.scatter" in line
                   and "update_window_dims = [1, 2, 3]" in line
                   for line in text.splitlines())

    chunk = extend_text({"obs": jnp.zeros((2, 16), jnp.int32),
                         "n_valid": jnp.full(2, 16, jnp.int32), **rows})
    tokens = jnp.zeros((2, 24), jnp.int32)
    _, prefix, _ = jax.eval_shape(net.apply_with_stats, params, tokens)
    jax.jit(lambda p, t: net.apply_with_stats(p, t)).lower(params, tokens)
    jax.jit(lambda p, t, s: net.apply_with_stats(p, t, s)).lower(
        params, tokens, prefix)
    assert not calls
    assert pool_scatters(chunk) == net.num_lightning
    decode = extend_text({"obs": jnp.zeros(2, jnp.int32), **rows})
    assert calls == [state["lightning"][0].shape] * net.num_lightning
    assert pool_scatters(decode) == 0


# -- the family's rows ---------------------------------------------------------


def test_the_seventh_net_is_a_row_and_keeps_slots(built):
    cfg = built["cfg"]
    assert DECODER_NETS["minicpm_sala_q"] is MiniCpmSalaQNet
    assert decoder_block(cfg.network) == ("minicpm_sala",
                                          cfg.network.minicpm_sala)
    assert fam.family_of(cfg) == "decoder_q"
    assert fam.keeps_slots(cfg) and fam.keeps_slots(built["net"])
    assert not fam.keeps_slots(get_config("ouro_tiny_q"))
    assert not hasattr(built["net"], "share")
    state = fam.episode_state(cfg, 5)
    assert set(state) == {"slot", "fresh"} and state["slot"] == 5
    assert fam.stored_state_spec("decoder_q", cfg) == {}
    # an older decoder keeps the token window
    assert set(fam.episode_state(get_config("ouro_tiny_q"), 5)) == {
        "ctx", "n"}


def test_param_count_of_the_published_stage():
    """ISSUE 55's arithmetic: a sparse layer 253,763,840, a lightning
    layer 285,225,216, the served stage (layers 9-16, the whole
    vocabulary) 2,820,569,088."""
    from ape_x_dqn_tpu.runtime.train import apply_overrides

    cfg = apply_overrides(get_config("minicpm_sala_9b_q"), [
        "network.minicpm_sala.num_hidden_layers=8",
        "network.minicpm_sala.mixer_types=('minicpm4','lightning-attn',"
        "'lightning-attn','lightning-attn','lightning-attn',"
        "'lightning-attn','lightning-attn','minicpm4')"])
    net = build_network(cfg.network, None)
    assert net.param_count() == 2_820_569_088
    whole = build_network(get_config("minicpm_sala_9b_q").network, None)
    assert whole.param_count() == (8 * 253_763_840 + 24 * 285_225_216
                                   + 2 * 300_843_008 + 4096)
    # 12 MiB of matrices a session, 2,112 B a position
    assert net._matrix_bytes() == 6 * 2 ** 21
    assert net._position_bytes() == 2 * 1056


def test_the_familys_loss_trains_the_tiny_preset():
    from ape_x_dqn_tpu.runtime.driver import ApexDriver
    from ape_x_dqn_tpu.runtime.learner import SingleChipLearner

    cfg = tiny()
    cfg = cfg.replace(actors=dataclasses.replace(cfg.actors, num_actors=0),
                      eval_episodes=0, eval_every_steps=0)
    driver = ApexDriver(cfg)
    try:
        assert type(driver.learner) is SingleChipLearner
        assert type(driver.net) is MiniCpmSalaQNet
        rng = np.random.default_rng(0)
        n, length = 16, cfg.replay.seq_length
        items = {"obs": rng.integers(0, 64, (n, length)).astype(np.int32),
                 "actions": rng.integers(0, 64, (n, length)).astype(np.int32),
                 "rewards": rng.normal(size=(n, length)).astype(np.float32),
                 "terminals": np.zeros((n, length), np.float32),
                 "mask": np.ones((n, length), np.float32)}
        state = driver.learner.add(driver.state, items, jnp.ones(n))
        before = jax.device_get(state.params)
        state, m = driver.learner.train_many(state, 2)
        assert int(state.step) == 2 and np.isfinite(float(m["loss"]))
        assert float(m["loop_block_applications"]) == 4
        after = jax.device_get(state.params)
        for layer, name in ((0, "q_proj"), (1, "k_proj"), (2, "o_gate"),
                            (3, "o_proj")):
            assert not np.array_equal(
                before["layers"][layer]["self_attn"][name],
                after["layers"][layer]["self_attn"][name]), (layer, name)
    finally:
        driver.server.stop()


def test_hbm_price_takes_the_slot_state_from_the_net(built):
    from ape_x_dqn_tpu.utils import hbm

    cfg, net = built["cfg"], built["net"]
    price = fam.hbm_price(cfg, net)
    slots, max_len, pool = fam.slot_geometry(cfg, net.slot_block)
    assert (slots, max_len, pool) == (3, 65, 3 * 72)
    assert price["slot_state"] == net.slot_state_bytes(slots, pool, max_len)
    held = net.slot_state(slots, pool, max_len)
    assert price["slot_state"] == sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(held))
    budget = hbm.check_hbm_fits(cfg, (), np.int32,
                                param_count=net.param_count(),
                                hbm_bytes=1 << 34, **price)
    assert budget.slot_state == price["slot_state"]
    assert "server slot state" in budget.table()
    # a net without one is priced as it was
    assert "slot_state" not in fam.hbm_price(
        get_config("ouro_tiny_q"),
        build_network(get_config("ouro_tiny_q").network, None))
