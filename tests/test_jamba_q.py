"""models/jamba_q.py at tiny widths on the CPU: the published sizes count
3,029,337,472 parameters by shapes alone, attention at layers 7 and 21;
a prefill in chunks then decode steps through the slot state is
benchmarks/reference/jamba_q.py's full forward pass on seeded weights,
logits compared; two sessions in one batch do not mix; `fresh` resets
both kinds of state; padding rows touch the scratch slot only; the
scopes are in the lowered `extend`; the counters count valid rows; the
net is a row of the family that keeps slots, and the family's loss
trains the tiny preset."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.configs import JambaConfig, get_config
from ape_x_dqn_tpu.models import DECODER_NETS, build_network, decoder_block
from ape_x_dqn_tpu.models.jamba_q import JambaQNet
from ape_x_dqn_tpu.ops import selective_scan
from ape_x_dqn_tpu.runtime import family as fam
from benchmarks.harness import jamba_params
from benchmarks.reference import jamba_q as ref

T = 56
MAX_LEN = 64
# small blocks and tiles, so that a session of 56 positions crosses
# seven blocks and four key tiles
BLOCK, TILES = 8, (8, 16)


def tiny():
    return get_config("jamba2_tiny_q")


@pytest.fixture(scope="module")
def built():
    cfg = tiny()
    net = JambaQNet(cfg.network.jamba, "float32", kv_block=BLOCK,
                    attn_tiles=TILES)
    params = net.init(jax.random.PRNGKey(0))
    tokens = np.random.default_rng(0).integers(0, 64, (2, T)).astype(np.int32)
    sizes = jamba_params.sizes(cfg.network.jamba)
    want = np.stack([np.asarray(ref.forward(
        jamba_params.reference_params(params), row, sizes))
        for row in tokens])
    extend = jax.jit(lambda p, s, i: net.extend(p, s, i, max_len=MAX_LEN),
                     donate_argnums=(1,))
    return {"cfg": cfg, "net": net, "params": params, "tokens": tokens,
            "want": want, "sizes": sizes, "extend": extend}


def test_param_count_of_the_published_sizes():
    """ISSUE 57's arithmetic, by shapes alone: a Mamba mixer 41,241,792,
    an attention mixer 13,762,560, the MLP 62,914,560; 26 + 2 layers,
    the tied embedding, the final norm."""
    net = build_network(get_config("jamba2_3b_q").network, None)
    assert type(net) is JambaQNet
    shapes = net.param_shapes()
    count = lambda tree: sum(                                  # noqa: E731
        int(np.prod(s)) for s in jax.tree.leaves(
            tree, is_leaf=lambda x: isinstance(x, tuple)))
    assert count(shapes["layers"][0]["mamba"]) == 41_241_792
    assert count(shapes["layers"][7]["self_attn"]) == 13_762_560
    assert count(shapes["layers"][0]["mlp"]) == 62_914_560
    assert count(shapes["layers"][0]) == 104_161_472
    assert count(shapes["layers"][7]) == 76_682_240
    assert [i for i, k in enumerate(net.kinds) if k == "attention"] == [7, 21]
    assert "lm_head" not in shapes          # tied: the embedding is the head
    assert net.param_count() == (26 * 104_161_472 + 2 * 76_682_240
                                 + 167_772_160 + 2_560) == 3_029_337_472
    # 358,400 B a Mamba layer and session, 1,024 B a position
    assert net._session_bytes() == 26 * 358_400 == 9_318_400
    assert net._position_bytes() == 1_024
    assert JambaConfig() == get_config("jamba2_3b_q").network.jamba


def test_seeded_decays_span_one_to_a_thousand_tokens(built):
    p = built["params"]["layers"][0]["mamba"]
    np.testing.assert_allclose(np.exp(p["A_log"][0]), np.arange(1, 5),
                               rtol=1e-6)
    step = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert 1e-3 * 0.999 <= step.min() and step.max() <= 1e-1 * 1.001
    assert np.asarray(p["D"]).tolist() == [1.0] * 128
    other = built["params"]["layers"][2]["mamba"]["dt_bias"]
    assert not np.array_equal(np.asarray(p["dt_bias"]), np.asarray(other))


def test_full_forward_matches_reference(built):
    """`apply` over the whole history against the reference's loop over
    positions, float32 both: what differs is the order of float32 sums
    (the conv's taps, the tile walk's online softmax), 1e-5 of logits of
    order 1; one bit less of bfloat16 would be 4e-3."""
    q, _ = jax.jit(built["net"].apply)(built["params"], built["tokens"])
    assert q.shape == (2, T, 64) and q.dtype == jnp.float32
    assert float(np.abs(built["want"]).max()) > 0.5
    np.testing.assert_allclose(q, built["want"], atol=1e-5, rtol=0)
    # and from a burn-in prefix's state
    apply = jax.jit(built["net"].apply)
    q1, state = apply(built["params"], built["tokens"][:, :24])
    q2, _ = apply(built["params"], built["tokens"][:, 24:], state)
    np.testing.assert_allclose(jnp.concatenate([q1, q2], 1), built["want"],
                               atol=1e-5, rtol=0)


def _prefill(built, state, lengths, slots, base, chunk):
    """Rows' prompts through `extend` in chunks of `chunk` -> (state, Q
    at each row's last prompt position, the counters summed)."""
    tokens, lengths = built["tokens"], np.asarray(lengths)
    done = np.zeros(len(lengths), int)
    last, counted = {}, {}
    while (done < lengths).any():
        take = np.minimum(lengths - done, chunk)
        obs = np.zeros((len(lengths), chunk), np.int32)
        for r, n in enumerate(take):
            obs[r, :n] = tokens[r, done[r]:done[r] + n]
        out, state = built["extend"](built["params"], state, {
            "obs": obs, "n_valid": take.astype(np.int32),
            "slot": np.asarray(slots, np.int32),
            "base": np.asarray(base, np.int32),
            "fresh": (done == 0).astype(np.int32)})
        for r, n in enumerate(take):
            if n:
                last[r] = np.asarray(out["q"][r])
        for k, v in out["counters"].items():
            counted[k] = counted.get(k, 0) + int(v)
        done += take
    return state, last, counted


@pytest.mark.parametrize("chunk", [5, 16, 40])
def test_prefill_then_decode_through_the_slot_state(built, chunk):
    """Two sessions of 33 and 20 prompt tokens in slots 2 and 0, ranges
    at blocks 8 and 0, prefilled in chunks of `chunk` (ragged: the
    shorter one's last chunks are all padding), then decoded one token a
    step in a 4-row batch padded with two scratch rows: every Q is the
    reference's at that position, to the float32 tolerance of the full
    forward's test."""
    net, want, tokens = built["net"], built["want"], built["tokens"]
    lengths, slots, base = [33, 20], [2, 0], [8, 0]
    state = net.slot_state(4, 4 * MAX_LEN, MAX_LEN)
    state, last, counted = _prefill(built, state, lengths, slots, base, chunk)
    for r in range(2):
        np.testing.assert_allclose(last[r], want[r, lengths[r] - 1],
                                   atol=1e-5, rtol=0)
    # four Mamba layers, two attention layers in the tiny stack
    assert counted["extend_tokens"] == 53
    assert counted["ssm_tokens_scanned"] == 4 * 53
    assert counted["attn_positions_read"] == 2 * sum(
        n * (n + 1) // 2 for n in lengths)
    assert counted["attn_positions_fetched"] == 0    # a decode step's
    pos = np.asarray(lengths)
    scratch_slot, scratch_base = 4, 4 * MAX_LEN // BLOCK
    for _ in range(T - min(lengths)):
        live = pos < T
        obs = [tokens[r, min(pos[r], T - 1)] for r in range(2)]
        out, state = built["extend"](built["params"], state, {
            "obs": np.asarray(obs + [0, 0], np.int32),
            "slot": np.asarray(slots + [scratch_slot] * 2, np.int32),
            "base": np.asarray(base + [scratch_base] * 2, np.int32),
            "fresh": np.asarray([0, 0, 1, 1], np.int32),
            "n_valid": np.asarray([*live, 0, 0], np.int32)})
        for r in range(2):
            if live[r]:
                np.testing.assert_allclose(out["q"][r], want[r, pos[r]],
                                           atol=1e-5, rtol=0)
        c = {k: int(v) for k, v in out["counters"].items()}
        # padding rows and rows past their end are not counted
        assert c["extend_tokens"] == live.sum()
        assert c["ssm_rows_updated"] == 4 * live.sum()
        assert c["ssm_tokens_scanned"] == 0
        assert c["attn_positions_read"] == 2 * int((pos + 1)[live].sum())
        # whole key tiles of 16 up to each row's own position (a row past
        # its end walks its range's four and no further), one tile each
        # padding row, in both attention layers
        walked = np.minimum(pos // TILES[1] + 1, MAX_LEN // TILES[1])
        assert c["attn_positions_fetched"] == 2 * TILES[1] * (
            int(walked.sum()) + 2)
        pos = pos + live
    assert np.asarray(net.slot_lengths(state)).tolist() == [T, 0, T, 0, 0]
    # what the slots hold of the recurrence is the reference's h after
    # the whole history, float32 against float32, in the mapper's layout
    params = jamba_params.reference_params(built["params"])
    for r in range(2):
        x = ref.embed(params, tokens[r], built["sizes"])
        held = jamba_params.device_state(state, slots[r])
        mi = 0
        for kind, p in zip(built["sizes"].kinds, params["layers"]):
            x, h = ref.block_and_state(p, x, built["sizes"], kind)
            if kind == ref.MAMBA:
                assert float(np.abs(h).max()) > 1e-3
                np.testing.assert_allclose(held[mi], h, atol=1e-6, rtol=0)
                mi += 1
        assert mi == len(held) == 4


@pytest.mark.parametrize("walk", ["attend_tiles", "attend_range"])
def test_the_key_count_is_read_off_the_applied_mask(built, monkeypatch, walk):
    """`attn_positions_read` comes from the mask the op applied: a mask
    one position short (the query not seeing its own key) reads one key
    a query and attention layer less, which is what the reference's
    `keys_attended` says of `attn_one_short` - in a prefill chunk's walk
    and, summed inside its kernel, in a decode step's."""
    from ape_x_dqn_tpu.ops import block_select_attention as bsa

    net = built["net"]
    lengths = [33, 20]
    sound = ref.keys_attended(lengths, built["sizes"])
    short = ref.keys_attended(lengths, built["sizes"]._replace(
        attn_one_short=True))
    assert sound == 2 * sum(n * (n + 1) // 2 for n in lengths)
    assert sound - short == 2 * sum(lengths)
    real = getattr(bsa, walk)

    def one_short(q, *rest, **kw):
        # t is attend_tiles' second argument and attend_range's fifth
        at = {"attend_tiles": 0, "attend_range": 3}[walk]
        rest = (*rest[:at], rest[at] - 1, *rest[at + 1:])
        return real(q, *rest, **kw)

    monkeypatch.setattr(bsa, walk, one_short)
    extend = jax.jit(lambda p, s, i: net.extend(p, s, i, max_len=MAX_LEN),
                     donate_argnums=(1,))
    state, _, counted = _prefill(
        {**built, "extend": extend}, net.slot_state(4, 4 * MAX_LEN, MAX_LEN),
        lengths, [2, 0], [8, 0], 16)
    if walk == "attend_tiles":
        assert counted["attn_positions_read"] == short
        return
    assert counted["attn_positions_read"] == sound
    steps = 3
    for i in range(steps):
        out, state = extend(built["params"], state, {
            "obs": built["tokens"][:, 40 + i], "fresh": np.zeros(2, np.int32),
            "slot": np.asarray([2, 0], np.int32),
            "base": np.asarray([8, 0], np.int32)})
        counted["attn_positions_read"] += int(
            out["counters"]["attn_positions_read"])
    after = [n + steps for n in lengths]
    # the decoded positions' queries each missed their own key in each of
    # the two attention layers
    assert counted["attn_positions_read"] == ref.keys_attended(
        after, built["sizes"]) - 2 * 2 * steps


def test_sessions_do_not_mix_and_fresh_resets_both_kinds(built):
    net = built["net"]
    both = net.slot_state(2, 2 * MAX_LEN, MAX_LEN)
    both, last, _ = _prefill(built, both, [30, 30], [0, 1], [0, 8], 16)
    alone = net.slot_state(2, 2 * MAX_LEN, MAX_LEN)
    alone, only, _ = _prefill(
        {**built, "tokens": built["tokens"][1:]}, alone, [30], [1], [8], 16)
    # the second session beside the first is what it is alone (to the
    # order of a float32 sum: the batch is another shape; the other
    # session's state would move it by a tenth)
    np.testing.assert_allclose(last[1], only[0], atol=1e-6, rtol=0)
    for kind in ("ssm", "conv"):
        for a, b in zip(both[kind], alone[kind]):
            np.testing.assert_allclose(a[1], b[1], atol=1e-6, rtol=0)
            assert float(jnp.abs(a[0]).max()) > 0
            assert not np.asarray(b[0]).any()
    # a fresh session in slot 0 starts from zeros whatever the slot held
    other = {**built, "tokens": built["tokens"][1:]}
    both, again, _ = _prefill(other, both, [30], [0], [0], 16)
    np.testing.assert_allclose(again[0], only[0], atol=1e-6, rtol=0)
    assert int(net.slot_lengths(both)[0]) == 30


def test_padding_rows_touch_the_scratch_slot_only(built):
    net = built["net"]
    state = net.slot_state(2, 2 * MAX_LEN, MAX_LEN)
    state, _, _ = _prefill(built, state, [30, 30], [0, 1], [0, 8], 16)
    before = jax.tree.map(np.asarray, state)
    scratch_base = 2 * MAX_LEN // BLOCK
    out, state = built["extend"](built["params"], state, {
        "obs": np.zeros(4, np.int32), "slot": np.full(4, 2, np.int32),
        "base": np.full(4, scratch_base, np.int32),
        "fresh": np.ones(4, np.int32), "n_valid": np.zeros(4, np.int32)})
    assert {k: int(v) for k, v in out["counters"].items()} == {
        "extend_tokens": 0, "ssm_rows_updated": 0, "ssm_tokens_scanned": 0,
        "attn_positions_read": 0,
        # one key tile a padding row and attention layer: nobody reads it
        "attn_positions_fetched": 4 * 2 * TILES[1]}
    after = jax.tree.map(np.asarray, state)
    for kind in ("ssm", "conv"):
        for a, b in zip(before[kind], after[kind]):
            np.testing.assert_array_equal(a[:2], b[:2])
    span = 2 * MAX_LEN          # the sessions' part of the pools
    for kind in ("k", "v"):
        for a, b in zip(before[kind], after[kind]):
            np.testing.assert_array_equal(a[:, :span], b[:, :span])
    np.testing.assert_array_equal(before["len"][:2], after["len"][:2])


@pytest.mark.parametrize("departure", [*jamba_params.DEPARTURES])
def test_the_reference_tells_each_departure_apart(built, departure):
    """Every departure the benchmark's check names moves the reference's
    own float32 logits (which of the check's rules SEES it at bfloat16's
    resolution is harness/slot_state_checks.py's matter)."""
    value = jamba_params.DEPARTURES[departure]
    if value == jamba_params.FROM_FIRST_COMPARED:
        value = 40
    far = ref.forward(
        jamba_params.reference_params(built["params"]), built["tokens"][0],
        jamba_params.sizes(built["cfg"].network.jamba, **{departure: value}))
    moved = np.abs(np.asarray(far) - built["want"][0])
    assert moved[40:].max() > 1e-5, departure
    if departure == "padding_advances_from":
        assert moved[:40].max() == 0.0


def test_the_scopes_are_in_the_lowered_extend(built):
    net, params = built["net"], built["params"]
    state = net.slot_state(2, 2 * MAX_LEN, MAX_LEN)
    rows = {"slot": jnp.zeros(2, jnp.int32), "base": jnp.zeros(2, jnp.int32),
            "fresh": jnp.ones(2, jnp.int32)}

    def text(inputs):
        return jax.jit(lambda p, s, i: net.extend(
            p, s, i, max_len=MAX_LEN)).lower(params, state, inputs).as_text(
                debug_info=True)

    decode = text({"obs": jnp.zeros(2, jnp.int32), **rows})
    chunk = text({"obs": jnp.zeros((2, 16), jnp.int32),
                  "n_valid": jnp.full(2, 16, jnp.int32), **rows})
    for name in ("jamba.embed", "jamba.mamba/jamba.mamba.in",
                 "jamba.mamba/jamba.mamba.conv",
                 "jamba.mamba/jamba.mamba.gates",
                 "jamba.mamba/jamba.mamba.scan",
                 "jamba.mamba/jamba.mamba.scan/slots.read",
                 "jamba.mamba/jamba.mamba.scan/slots.write",
                 "jamba.mamba/jamba.mamba.out", "jamba.attn/jamba.attn.proj",
                 "jamba.attn/slots.write", "jamba.attn/jamba.attn.attend",
                 "jamba.attn/jamba.attn.out", "jamba.mlp", "jamba.head",
                 "slots.read", "slots.write"):
        assert name in decode and name in chunk, name


def test_the_slot_kernel_is_the_decode_steps_alone(built, monkeypatch):
    """`selective_scan.step_slots` is traced once a Mamba layer by a
    decode step and never by a prefill chunk or the learner's pass
    (without and with a burn-in state): their programs are the gather,
    `selective_scan.chunked` and the scatter they were (at PR 58 their
    lowered text was the parent commit's to the byte), and a decode step
    gathers and scatters no state - only the conv tails' rows."""
    net, params = built["net"], built["params"]
    calls, kernel = [], selective_scan.step_slots

    def counted(*args):
        calls.append(args[0].shape)
        return kernel(*args)

    monkeypatch.setattr(selective_scan, "step_slots", counted)
    state = net.slot_state(2, 2 * MAX_LEN, MAX_LEN)
    rows = {"slot": jnp.zeros(2, jnp.int32), "base": jnp.zeros(2, jnp.int32),
            "fresh": jnp.ones(2, jnp.int32)}

    def extend_text(inputs):
        return jax.jit(lambda p, s, i: net.extend(
            p, s, i, max_len=MAX_LEN)).lower(params, state, inputs).as_text()

    pool = "tensor<{}x{}x{}xf32>".format(*state["ssm"][0].shape)

    def moved(text, op):          # states out of or into a layer's pool
        lines = text.splitlines()
        # (a scatter's types are on the line that closes its region,
        # three lines down)
        return sum(f"stablehlo.{op}" in line and pool in " ".join(
            lines[i:i + 4]) for i, line in enumerate(lines))

    chunk = extend_text({"obs": jnp.zeros((2, 16), jnp.int32),
                         "n_valid": jnp.full(2, 16, jnp.int32), **rows})
    tokens = jnp.zeros((2, 24), jnp.int32)
    _, prefix, _ = jax.eval_shape(net.apply_with_stats, params, tokens)
    jax.jit(lambda p, t: net.apply_with_stats(p, t)).lower(params, tokens)
    jax.jit(lambda p, t, s: net.apply_with_stats(p, t, s)).lower(
        params, tokens, prefix)
    assert not calls
    assert moved(chunk, "gather") == moved(chunk, "scatter") == net.num_mamba
    decode = extend_text({"obs": jnp.zeros(2, jnp.int32), **rows})
    assert calls == [state["ssm"][0].shape] * net.num_mamba
    assert moved(decode, "gather") == moved(decode, "scatter") == 0


def test_the_eighth_net_is_a_row_and_keeps_slots(built):
    cfg = built["cfg"]
    assert DECODER_NETS["jamba_q"] is JambaQNet
    assert decoder_block(cfg.network) == ("jamba", cfg.network.jamba)
    assert fam.family_of(cfg) == "decoder_q"
    net = build_network(cfg.network, None)
    assert fam.keeps_slots(cfg) and fam.keeps_slots(net)
    assert not hasattr(net, "share")
    state = fam.episode_state(cfg, 5)
    assert set(state) == {"slot", "fresh"} and state["slot"] == 5
    # the server is handed the slot path by what the net offers
    plan = fam.server_slots(cfg, net)["slots"]
    assert plan.pool.block == net.slot_block == 128
    assert fam.server_apply_fn("decoder_q", net, cfg).__name__ == (
        "apply_slots")
    price = fam.hbm_price(cfg, net)
    slots, max_len, pool = fam.slot_geometry(cfg, net.slot_block)
    assert (slots, max_len, pool) == (3, 65, 3 * 128)
    assert price["slot_state"] == net.slot_state_bytes(
        slots, pool, max_len) == sum(
        x.size * x.dtype.itemsize
        for x in jax.tree.leaves(net.slot_state(slots, pool, max_len)))


def test_the_familys_loss_trains_the_tiny_preset(monkeypatch):
    from ape_x_dqn_tpu.ops import selective_scan
    from ape_x_dqn_tpu.runtime.driver import ApexDriver
    from ape_x_dqn_tpu.runtime.learner import SingleChipLearner

    # a chunk's positions are unrolled in the program, forward and
    # backward, in both nets: four of them compile in a third of the time
    monkeypatch.setattr(selective_scan, "CHUNK", 4)
    cfg = tiny()
    cfg = cfg.replace(actors=dataclasses.replace(cfg.actors, num_actors=0),
                      eval_episodes=0, eval_every_steps=0)
    driver = ApexDriver(cfg)
    try:
        assert type(driver.learner) is SingleChipLearner
        assert type(driver.net) is JambaQNet
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
        assert float(m["loop_block_applications"]) == 6
        after = jax.device_get(state.params)
        for layer, group, name in ((0, "mamba", "in_proj"),
                                   (0, "mamba", "A_log"),
                                   (2, "mamba", "dt_bias"),
                                   (1, "self_attn", "k_proj"),
                                   (5, "mamba", "out_proj")):
            assert not np.array_equal(before["layers"][layer][group][name],
                                      after["layers"][layer][group][name]), (
                layer, name)
    finally:
        driver.server.stop()
