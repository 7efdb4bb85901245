"""The R2D2 learner against the plain float32 reference
(benchmarks/reference/r2d2.py) at a tiny size on the CPU: seeded
weights, the program's own r2d2 learner through `sample_k` /
`learn_k`, forward (Q [B, L, A]), loss, written priorities and the
gradient of every parameter.

The K chunks of one draw are followed one by one with the system's own
`_sgd_step`, so chunk j is compared at the parameters the learner holds
when it trains on it; `learn_k` on the same draw must then report the
last chunk's loss and have written, for every chunk, the priorities the
reference computes.

float32 compute is held tight: 1e-5 of the tensor's scale (float32
sums in two orders over a few thousand terms). bfloat16 compute is
held by the rule the chip-side check uses, at a batch of 32 so that a
95th percentile means something: errors in units of what the reference
itself errs by when rounded to bfloat16's mantissa
(benchmarks/harness/sequence_checks.py says where the limits come
from), and the reference rounded to two bits less has to fail it.
"""

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.configs import LearnerConfig, ReplayConfig
from ape_x_dqn_tpu.models import ApeXLSTMQNet
from ape_x_dqn_tpu.replay.prioritized import PrioritizedReplay
from ape_x_dqn_tpu.replay.sequence import (batch_to_sequence_batch,
                                           sequence_item_spec)
from ape_x_dqn_tpu.runtime.family import r2d2_family
from ape_x_dqn_tpu.runtime.learner import SingleChipLearner
from benchmarks.harness import correctness, r2d2_params, sequence_checks
from benchmarks.reference import r2d2 as ref

B, L, BURN_IN, N_STEP, LSTM, ACTIONS = 4, 8, 3, 2, 32, 5
GAMMA, ETA = 0.99, 0.9
CAPACITY, FILLED = 64, 48
# the bfloat16 cases: 32 sequences a chunk, drawn from enough of them
# that most leaves of a K-draw are drawn once, so that a 95th
# percentile over a chunk's written priorities means something
B_BF16, CAPACITY_BF16, FILLED_BF16 = 32, 512, 448
F32_TOL = 1e-5
# bf16 gradients: relative L2 error of a layer's gradient (kernel and
# bias together: a bias gradient alone is a sum of signed TD errors that
# all but cancels at batch 32, and its relative error says nothing) on
# fresh nets. The backward pass rounds every activation gradient to 8
# bits of mantissa again, through L cell updates: measured here 0.2-8.1%
# (PR 26, CPU), so twice the largest. (After training the gradients
# themselves shrink to the size of the rounding and a relative error
# means nothing: 5-2,000% after 2,000 steps. Only fresh nets are held.)
BF16_GRAD_REL = 0.15

LAYOUTS = {
    # frame mode at 36x36 (the smallest frame the Nature torso takes):
    # one packed row per sequence
    "frame": dict(obs_shape=(36, 36, 4), frame_mode=True),
    # frame mode at 60x60: 11 frames are 39,600 B, wider than a TPU
    # gather fetches whole, so the sequence is stored one row per frame
    "frame_rows": dict(obs_shape=(60, 60, 4), frame_mode=True),
    # vector observations, MLP torso, per-step storage
    "flat": dict(obs_shape=(6,), frame_mode=False),
}


def _items(rng, layout, n, masked):
    shape = layout["obs_shape"]
    if layout["frame_mode"]:
        obs = {"seq_frames": rng.integers(
            0, 256, (n, L + shape[2] - 1, *shape[:2]), dtype=np.uint8)}
    else:
        obs = {"obs": rng.normal(size=(n, L, *shape)).astype(np.float32)}
    t = np.arange(L)
    if masked:
        # episode tails: a valid length in [burn_in + 1, L], the episode's
        # terminal on the last valid step of most, and terminals inside
        n_valid = rng.integers(BURN_IN + 1, L + 1, n)
        mask = (t[None] < n_valid[:, None])
        ends = (t[None] == n_valid[:, None] - 1) & (rng.random(n) < 0.7
                                                    )[:, None]
        terminals = (mask & (ends | (rng.random((n, L)) < 0.1)))
    else:
        mask = np.ones((n, L), bool)
        terminals = np.zeros((n, L), bool)
    return {
        **obs,
        "actions": np.where(mask, rng.integers(0, ACTIONS, (n, L)), 0
                            ).astype(np.int32),
        "rewards": np.where(mask, rng.integers(-1, 2, (n, L)), 0
                            ).astype(np.float32),
        "terminals": terminals.astype(np.float32),
        "mask": mask.astype(np.float32),
        "init_c": rng.uniform(-0.5, 0.5, (n, LSTM)).astype(np.float32),
        "init_h": rng.uniform(-0.5, 0.5, (n, LSTM)).astype(np.float32),
    }


def _build(layout_name, k, masked, dtype):
    layout = LAYOUTS[layout_name]
    shape = layout["obs_shape"]
    pixels = layout["frame_mode"]
    net = ApeXLSTMQNet(num_actions=ACTIONS, lstm_size=LSTM, dense=64,
                       compute_dtype=dtype, mlp_torso=not pixels,
                       mlp_hidden=16)
    obs_dtype = np.uint8 if pixels else np.float32
    z = jnp.zeros((1, LSTM), jnp.float32)
    params = net.init(jax.random.PRNGKey(1),
                      np.zeros((1, 1, *shape), obs_dtype), (z, z))
    batch, capacity, filled = (
        (B, CAPACITY, FILLED) if dtype == "float32"
        else (B_BF16, CAPACITY_BF16, FILLED_BF16))
    lcfg = LearnerConfig(batch_size=batch, n_step=N_STEP, gamma=GAMMA,
                         value_rescale=True, target_sync_every=10 ** 6,
                         lr=1e-3, sample_chunk=k)
    rcfg = ReplayConfig(kind="sequence", capacity=capacity, seq_length=L,
                        burn_in=BURN_IN, priority_eta=ETA)
    replay = PrioritizedReplay(
        capacity, alpha=rcfg.alpha, beta=rcfg.beta, eps=rcfg.eps,
        item_spec=sequence_item_spec(shape, obs_dtype, L, LSTM,
                                     frame_mode=pixels))
    learner = SingleChipLearner(
        r2d2_family(lambda p, o, s: net.apply(p, o, s), lcfg, rcfg),
        replay, lcfg)
    state = learner.init(params, replay.init(), jax.random.PRNGKey(2))
    # a target net that differs from the online net, as after a sync
    state = state._replace(target_params=net.init(
        jax.random.PRNGKey(3), np.zeros((1, 1, *shape), obs_dtype), (z, z)))
    rng = np.random.default_rng(4)
    items = _items(rng, layout, filled, masked)
    state = learner.add(state, jax.tree.map(jnp.asarray, items),
                        jnp.asarray(rng.uniform(0.05, 2.0, filled),
                                    jnp.float32))
    return net, learner, state, rcfg


def _reference_step(online, target, items, weights):
    """-> (loss, aux, gradients) of the reference on one drawn chunk."""
    obs = sequence_checks.observations(items)
    args = (obs, items["actions"], items["rewards"], items["terminals"],
            items["mask"], items["init_c"], items["init_h"], weights)
    (loss, aux), grads = ref.loss_and_gradients(
        online, target, *args, burn_in=BURN_IN, n_step=N_STEP,
        gamma=GAMMA, eta=ETA)
    q_full, _ = ref.unroll(online, obs, (items["init_c"],
                                         items["init_h"]))
    return float(loss), aux, grads, np.asarray(q_full)


def _follow(net, learner, state, k):
    """One K-draw through the system and the reference, chunk by chunk.
    -> per-chunk comparisons and what `learn_k` wrote."""
    sample, rng = learner.sample_k(state, k)
    items_k, idx_k, w_k, _ = jax.tree.map(np.asarray, sample)
    target = r2d2_params.reference_params(
        jax.device_get(state.target_params))
    params, target_sys, opt, step = (state.params, state.target_params,
                                     state.opt_state, state.step)
    sgd = jax.jit(learner._sgd_step)
    grad_fn = jax.jit(jax.value_and_grad(learner.family.loss_fn, has_aux=True))
    chunks = []
    for j in range(k):
        items = jax.tree.map(lambda x: x[j], items_k)
        batch = batch_to_sequence_batch(jax.tree.map(jnp.asarray, items))
        (loss, aux), grads = grad_fn(params, target_sys, batch,
                                     jnp.asarray(w_k[j]))
        q_sys, _ = net.apply(params, batch.obs, tuple(batch.init_state))
        online = r2d2_params.reference_params(jax.device_get(params))
        r_loss, r_aux, r_grads, r_q = _reference_step(
            online, target, items, w_k[j])
        chunks.append(dict(
            loss=float(loss), priorities=np.asarray(aux["td_abs"]),
            grads=jax.device_get(grads), q=np.asarray(q_sys),
            valid_frac=float(aux["valid_frac"]), items=items,
            weights=w_k[j], online=online, ref_loss=r_loss,
            ref_priorities=np.asarray(r_aux["priorities"]),
            ref_valid=np.asarray(r_aux["valid"]), ref_q=r_q,
            ref_grads=r2d2_params.system_gradients(
                r_grads, jax.device_get(params))))
        params, target_sys, opt, step, _, _ = sgd(
            params, target_sys, opt, step,
            jax.tree.map(jnp.asarray, items), jnp.asarray(w_k[j]))
    # learn_k donates the state: everything above is on the host by now
    state, m = learner.learn_k(state._replace(rng=rng), sample, k)
    tree = np.asarray(state.replay.tree)
    once = correctness.drawn_once(idx_k.reshape(-1)).reshape(idx_k.shape)
    return chunks, float(m["loss"]), tree[tree.size // 2 + idx_k], once, \
        int(state.step)


@functools.lru_cache(maxsize=None)
def _case(layout, k, masked, dtype):
    """One case, built and followed once for every test that reads it."""
    net, learner, state, rcfg = _build(layout, k, masked, dtype)
    start = (r2d2_params.reference_params(jax.device_get(state.params)),
             r2d2_params.reference_params(
                 jax.device_get(state.target_params)))
    return (*_follow(net, learner, state, k), start,
            SimpleNamespace(replay=rcfg, learner=learner.lcfg))


def _rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-12))


def _by_layer(grads, ref_grads) -> dict:
    """layer path -> (its gradient leaves as one vector, the
    reference's): kernel and bias of a layer together."""
    layers = {}
    for (path, g), want in zip(jax.tree.leaves_with_path(grads),
                               jax.tree.leaves(ref_grads)):
        got, ref_ = layers.setdefault(jax.tree_util.keystr(path[:-1]),
                                      ([], []))
        got.append(np.ravel(g))
        ref_.append(np.ravel(want))
    return {k: (np.concatenate(g), np.concatenate(w))
            for k, (g, w) in layers.items()}


def _held(c, priorities):
    """A chunk of `_follow` as `sequence_checks.matches_reference`
    takes the system: the trained steps' Q, |delta|-space priorities,
    the loss."""
    return {"q": c["q"][:, BURN_IN:], "priorities": np.asarray(priorities),
            "loss": c["loss"]}


def _at(c, target, cfg, mantissa_bits):
    """The reference on a chunk's items at the parameters the learner
    held for it, float32 proper or rounded to `mantissa_bits`."""
    strides = (4, 2, 1) if "seq_frames" in c["items"] else ()
    return sequence_checks.reference_on(
        c["online"], target, c["items"], c["weights"], cfg, strides,
        mantissa_bits)


def _refs(c, target, cfg):
    """-> (the float32 reference, the reference at bfloat16's mantissa)
    on a chunk, computed once."""
    if "refs" not in c:
        c["refs"] = (_at(c, target, cfg, None),
                     _at(c, target, cfg,
                         sequence_checks.STATED_MANTISSA_BITS))
    return c["refs"]


BF16_CASES = [("frame", 1, False, "bfloat16"), ("flat", 4, True, "bfloat16"),
              ("frame_rows", 1, True, "bfloat16")]
CASES = [(layout, k, masked, "float32")
         for layout in ("frame", "flat") for k in (1, 4)
         for masked in (False, True)]
CASES += [("frame_rows", 4, True, "float32"), *BF16_CASES]


@pytest.mark.parametrize(
    "layout,k,masked,dtype", CASES,
    ids=[f"{a}-K{k}-{'masked_tail' if m else 'fresh'}-{d}"
         for a, k, m, d in CASES])
def test_sequence_learner_agrees_with_the_reference(layout, k, masked,
                                                    dtype):
    chunks, learn_loss, written, once, step, (_, start_target), cfg = \
        _case(layout, k, masked, dtype)
    rcfg = cfg.replay
    assert step == k
    exact = dtype == "float32"
    for c in chunks:
        q_scale = float(np.abs(c["ref_q"]).mean())
        w_mean = float(c["weights"].mean())
        # the reference's own statement of which steps train
        assert c["valid_frac"] == pytest.approx(
            float(c["ref_valid"].mean()), abs=1e-6)
        if exact:
            np.testing.assert_allclose(c["q"], c["ref_q"], rtol=0,
                                       atol=F32_TOL * max(q_scale, 1.0))
            assert c["loss"] == pytest.approx(c["ref_loss"], rel=1e-4,
                                              abs=F32_TOL)
            np.testing.assert_allclose(c["priorities"],
                                       c["ref_priorities"], rtol=1e-4,
                                       atol=F32_TOL)
            flat_sys = jax.tree.leaves_with_path(c["grads"])
            flat_ref = jax.tree.leaves(c["ref_grads"])
            assert len(flat_sys) == len(flat_ref)
            for (path, g), want in zip(flat_sys, flat_ref):
                scale = max(float(np.abs(want).max()), 1e-8)
                np.testing.assert_allclose(
                    g, want, rtol=0, atol=10 * F32_TOL * scale,
                    err_msg=jax.tree_util.keystr(path))
        else:
            ok, notes = sequence_checks.matches_reference(
                _held(c, c["priorities"]), *_refs(c, start_target, cfg),
                np.ones(len(c["weights"]), bool), w_mean)
            assert ok, notes
            for layer, (g, want) in _by_layer(c["grads"],
                                              c["ref_grads"]).items():
                assert _rel_l2(g, want) <= BF16_GRAD_REL, (
                    layer, _rel_l2(g, want))
    # learn_k on the same draw: the last chunk's loss, and for every
    # chunk the priorities of ITS parameters at ITS leaves
    last = chunks[-1]
    assert learn_loss == pytest.approx(last["loss"], rel=1e-5, abs=1e-7)
    alpha, eps = rcfg.alpha, rcfg.eps
    for j, c in enumerate(chunks):
        back = np.maximum(written[j], 0.0) ** (1.0 / alpha) - eps
        if exact:
            np.testing.assert_allclose(back[once[j]],
                                       c["ref_priorities"][once[j]],
                                       rtol=1e-3, atol=F32_TOL)
        else:
            ok, notes = sequence_checks.matches_reference(
                _held(c, back), *_refs(c, start_target, cfg), once[j],
                float(c["weights"].mean()))
            assert ok, (j, notes)


def test_the_chip_side_comparison_agrees_and_turns_false_when_perturbed():
    """`sequence_checks.agrees_with_reference`, the function behind the
    cell's `correct`: true on the system's own numbers, false when the
    reference is given other rewards, other frames or other weights,
    when the loss is off, and false when one byte of a sampled sequence
    differs."""
    chunks, _, written, once, _, (online, target), cfg = _case(
        "frame_rows", 1, True, "bfloat16")
    c = chunks[0]

    def match(items=c["items"], online=online, loss=c["loss"]):
        return sequence_checks.agrees_with_reference(
            online, target, items, c["weights"], c["q"], loss, written[0],
            once[0], cfg, (4, 2, 1))

    ok, notes = match()
    assert ok, notes
    bumped = dict(c["items"], rewards=c["items"]["rewards"] + 0.5
                  * c["items"]["mask"])
    ok, notes = match(items=bumped)
    assert not ok and notes["ok"]["q"] and not notes["ok"]["priorities"]
    rolled = dict(c["items"], seq_frames=np.roll(c["items"]["seq_frames"],
                                                 1, axis=0))
    assert not match(items=rolled)[1]["ok"]["q"]
    other = online._replace(advantage_kernel=online.advantage_kernel * 1.5)
    assert not match(online=other)[1]["ok"]["q"]
    ok, notes = match(loss=c["loss"] + 2 * notes["loss_allow"])
    assert not ok and not notes["ok"]["loss"]
    flipped = dict(c["items"])
    flipped["seq_frames"] = c["items"]["seq_frames"].copy()
    flipped["seq_frames"][2, 5, 7, 9] ^= 1
    ok, wrong = sequence_checks.sequences_are_what_was_written(
        flipped, c["items"])
    assert not ok and wrong["sequences_wrong"]["seq_frames"] == 1
    assert sequence_checks.sequences_are_what_was_written(
        c["items"], c["items"])[0]


@pytest.mark.parametrize(
    "layout,k,masked,dtype", BF16_CASES,
    ids=[f"{a}-K{k}-{'masked_tail' if m else 'fresh'}"
         for a, k, m, _ in BF16_CASES])
def test_two_bits_of_mantissa_less_fail(layout, k, masked, dtype):
    """The reference computed with two bits of mantissa less than the
    bfloat16 the configuration states (every weight, input, layer
    result, gate and state update rounded to 5 explicit bits) is put
    through the comparison that decides `correct`, where the system
    passes it: it has to fail, in the Q-values, on every chunk. At one
    bit less it errs about twice bfloat16's unit, at two bits four
    times; the limit is 2.0 units (sequence_checks.py has the chip's
    readings)."""
    chunks, _, _, _, _, (_, target), cfg = _case(layout, k, masked, dtype)
    for c in chunks:
        want, stated = _refs(c, target, cfg)
        everywhere = np.ones(len(c["weights"]), bool)
        w_mean = float(c["weights"].mean())
        units = {}
        for bits in (6, sequence_checks.LOWER_MANTISSA_BITS):
            ok, notes = sequence_checks.matches_reference(
                _at(c, target, cfg, bits), want, stated, everywhere,
                w_mean)
            units[bits] = notes["q_err_q95"] / notes["q_unit"]
        assert not ok and not notes["ok"]["q"], notes
        assert 1.4 < units[6] < 3.0 < units[5] < 6.0, units
