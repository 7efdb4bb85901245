"""The sequence family's pixel path (PR 27): conv1's input is prepared
once per SGD step — stacked, scaled to the compute dtype — and the four
net applications of the R2D2 loss read time-slices of that one array.

Held here, on the CPU at tiny widths:

(i)   `_sgd_step` on the prepared batch gives, bit for bit, what the
      parent's formulation gives — written out below: uint8 stacks on
      the last axis, `net_apply_seq` scaling them in every application —
      in Q, loss, priorities and every gradient leaf, for the three
      storage layouts and both compute dtypes;
(ii)  the jaxpr of `_sgd_step` converts every pixel conv1 reads from
      uint8 to float exactly once (the parent's: twice, in four converts);
(iii) `net_apply_seq` still takes uint8 stacks (the call the benchmark's
      check makes) and `sample_k` still returns the stored uint8 items;
(iv)  the dist learner at dp=1 takes the same prepared batch;
(v)   since ISSUE 42 the packed store's rows are 32-bit words and a
      sample hands a split leaf's rows on beside its bytes:
      `batch_to_sequence_batch` on those word rows gives the plain
      `jnp.stack` form element for element at every cut of the time
      axis, widens no byte to a word on the way, and any other depth or
      dtype still takes the plain form.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ape_x_dqn_tpu.configs import LearnerConfig, ReplayConfig
from ape_x_dqn_tpu.models import ApeXLSTMQNet
from ape_x_dqn_tpu.models.base import dtype_of, preprocess_obs
from ape_x_dqn_tpu.ops.losses import SequenceBatch
from ape_x_dqn_tpu.parallel.dist_learner import DistLearner
from ape_x_dqn_tpu.parallel.mesh import make_mesh
from ape_x_dqn_tpu.replay.packing import WORDS, PixelPacker
from ape_x_dqn_tpu.replay.prioritized import PrioritizedReplay
from ape_x_dqn_tpu.replay.sequence import (batch_to_sequence_batch,
                                           sequence_item_spec)
from ape_x_dqn_tpu.runtime.family import r2d2_family
from ape_x_dqn_tpu.runtime.learner import SingleChipLearner

B, L, BURN_IN, N_STEP, LSTM, ACTIONS, STACK = 4, 8, 3, 2, 16, 5, 4
CAPACITY, FILLED = 32, 24

LAYOUTS = {
    # single frames, one packed row per sequence
    "frame": dict(hw=(36, 36), frame_mode=True),
    # single frames at 60x60: 11 frames are wider than a TPU gather
    # fetches whole, so the sequence is stored one row per frame
    "frame_rows": dict(hw=(60, 60), frame_mode=True),
    # the stacks themselves stored as `obs`: nothing to rebuild
    "flat": dict(hw=(36, 36), frame_mode=False),
}
DTYPES = ("float32", "bfloat16")


def _items(rng, layout, n):
    h, w = layout["hw"]
    frames = rng.integers(0, 256, (n, L + STACK - 1, h, w), dtype=np.uint8)
    if layout["frame_mode"]:
        obs = {"seq_frames": frames}
    else:
        obs = {"obs": np.stack([frames[:, c:c + L] for c in range(STACK)],
                               axis=-1)}
    t = np.arange(L)
    n_valid = rng.integers(BURN_IN + 2, L + 1, n)
    mask = t[None] < n_valid[:, None]
    terminals = mask & (rng.random((n, L)) < 0.1)
    return {
        **obs,
        "actions": rng.integers(0, ACTIONS, (n, L)).astype(np.int32),
        "rewards": rng.integers(-1, 2, (n, L)).astype(np.float32),
        "terminals": terminals.astype(np.float32),
        "mask": mask.astype(np.float32),
        "init_c": rng.uniform(-0.5, 0.5, (n, LSTM)).astype(np.float32),
        "init_h": rng.uniform(-0.5, 0.5, (n, LSTM)).astype(np.float32),
    }


def _build(layout_name, dtype, dist=False):
    layout = LAYOUTS[layout_name]
    shape = (*layout["hw"], STACK)
    net = ApeXLSTMQNet(num_actions=ACTIONS, lstm_size=LSTM, dense=32,
                       compute_dtype=dtype)
    z = jnp.zeros((1, LSTM), jnp.float32)
    zero_obs = np.zeros((1, 1, *shape), np.uint8)
    params = net.init(jax.random.PRNGKey(1), zero_obs, (z, z))
    lcfg = LearnerConfig(batch_size=B, n_step=N_STEP, gamma=0.99,
                         value_rescale=True, target_sync_every=10 ** 6,
                         lr=1e-3, sample_chunk=1)
    rcfg = ReplayConfig(kind="sequence", capacity=CAPACITY, seq_length=L,
                        burn_in=BURN_IN, priority_eta=0.9)
    spec = sequence_item_spec(shape, np.uint8, L, LSTM,
                              frame_mode=layout["frame_mode"])
    replay = PrioritizedReplay(CAPACITY, alpha=rcfg.alpha, beta=rcfg.beta,
                               eps=rcfg.eps, item_spec=spec)
    apply = lambda p, o, s: net.apply(p, o, s)  # noqa: E731
    rng = np.random.default_rng(4)
    items = jax.tree.map(jnp.asarray, _items(rng, layout, FILLED))
    pri = jnp.asarray(rng.uniform(0.05, 2.0, FILLED), jnp.float32)
    if dist:
        learner = DistLearner(
            r2d2_family(apply, lcfg, rcfg, compute_dtype=dtype_of(dtype)),
            replay, lcfg, make_mesh(dp=1, tp=1))
        state = learner.init(params, spec, jax.random.PRNGKey(2))
        state = learner.add(state, jax.tree.map(lambda x: x[None], items),
                            pri[None])
    else:
        learner = SingleChipLearner(
            r2d2_family(apply, lcfg, rcfg, compute_dtype=dtype_of(dtype)),
            replay, lcfg)
        state = learner.init(params, replay.init(), jax.random.PRNGKey(2))
        state = learner.add(state, items, pri)
    # a target net that differs from the online net, as after a sync
    state = state._replace(target_params=jax.tree.map(
        lambda p, q: p.astype(q.dtype).reshape(q.shape),
        net.init(jax.random.PRNGKey(3), zero_obs, (z, z)),
        state.target_params))
    return net, learner, state


def _parent_batch(items):
    """The batch as the parent built it: uint8 stacks on the last axis,
    left for `net_apply_seq` to scale in each of its four applications."""
    if "seq_frames" in items:
        f = items["seq_frames"]
        obs = jnp.stack([f[:, c:c + L] for c in range(STACK)], axis=-1)
    else:
        obs = items["obs"]
    assert obs.dtype == jnp.uint8
    return SequenceBatch(
        obs=obs, actions=items["actions"], rewards=items["rewards"],
        terminals=items["terminals"], mask=items["mask"],
        init_state=(items["init_c"], items["init_h"]))


def _parent_sgd_step(learner, params, target_params, opt_state, items, w):
    (loss, aux), grads = jax.value_and_grad(
        learner.family.loss_fn, has_aux=True)(
        params, target_params, _parent_batch(items), w)
    updates, _ = learner.optimizer.update(grads, opt_state, params)
    return loss, aux["td_abs"], grads, optax.apply_updates(params, updates)


def _draw(learner, state):
    sample, _ = learner.sample_k(state, 1)
    items_k, _, w_k, _ = sample
    return jax.tree.map(lambda x: x[0], items_k), w_k[0]


def _bits(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_sgd_step_equals_the_parents_formulation_bit_for_bit(layout, dtype):
    net, learner, state = _build(layout, dtype)
    items, w = _draw(learner, state)
    args = (state.params, state.target_params, state.opt_state)

    def new(params, target, opt, items, w):
        grads = jax.grad(lambda p: learner.family.loss_fn(
            p, target, learner.family.make_batch(items), w)[0])(params)
        out = learner._sgd_step(params, target, opt, jnp.int32(0), items, w)
        return out[5]["loss"], out[4], grads, out[0]

    got = jax.jit(new)(*args, items, w)
    want = jax.jit(lambda *a: _parent_sgd_step(learner, *a))(
        *args, items, w)
    for name, g, v in zip(("loss", "priorities", "gradients", "params"),
                          got, want):
        for a, b in zip(_bits(g), _bits(v)):
            np.testing.assert_array_equal(a, b, err_msg=name)
    assert np.isfinite(float(got[0])) and float(got[0]) > 0
    assert all(np.abs(g).max() > 0 for g in _bits(got[2])[:2])
    # Q over the whole sequence, from the prepared array and from the
    # parent's uint8 stacks
    # (inside one jit each, as `_sgd_step` runs them: XLA rewrites the
    # division by 255 the same way on both sides)
    state0 = (items["init_c"], items["init_h"])
    q_new, _ = jax.jit(lambda p, it: net.apply(
        p, learner.family.make_batch(it).obs, state0))(state.params, items)
    q_old, _ = jax.jit(lambda p, it: net.apply(
        p, _parent_batch(it).obs, state0))(state.params, items)
    np.testing.assert_array_equal(np.asarray(q_new), np.asarray(q_old))


def _sub_jaxprs(params):
    for v in params.values():
        for x in (v if isinstance(v, (list, tuple)) else (v,)):
            x = getattr(x, "jaxpr", x)      # a ClosedJaxpr holds one
            if hasattr(x, "eqns"):
                yield x


def _pixel_converts(jaxpr, at_least):
    """Sizes of every uint8 -> float convert of `at_least` elements or
    more in the jaxpr, sub-jaxprs included."""
    sizes = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "convert_element_type":
            src, dst = eqn.invars[0].aval, eqn.outvars[0].aval
            if (src.dtype == jnp.uint8 and src.size >= at_least
                    and jnp.issubdtype(dst.dtype, jnp.floating)):
                sizes.append(int(src.size))
        for sub in _sub_jaxprs(eqn.params):
            sizes += _pixel_converts(sub, at_least)
    return sizes


@pytest.mark.parametrize("dtype", DTYPES)
def test_every_pixel_conv1_reads_is_converted_once(dtype):
    _, learner, state = _build("frame_rows", dtype)
    items, w = _draw(learner, state)
    h, w_ = LAYOUTS["frame_rows"]["hw"]
    one_frame_a_step = B * L * h * w_
    stacked = one_frame_a_step * STACK      # what conv1 reads per step
    args = (state.params, state.target_params, state.opt_state)
    new = jax.make_jaxpr(lambda *a: learner._sgd_step(
        a[0], a[1], a[2], jnp.int32(0), a[3], a[4]))(*args, items, w)
    sizes = _pixel_converts(new.jaxpr, one_frame_a_step // 2)
    # one convert per time-slice the loss cuts (burn-in, trained steps),
    # each pixel of the prepared array once
    assert len(sizes) == 2 and sum(sizes) == stacked, sizes
    old = jax.make_jaxpr(lambda *a: _parent_sgd_step(learner, *a))(
        *args, items, w)
    sizes = _pixel_converts(old.jaxpr, one_frame_a_step // 2)
    # online and target net, burn-in and trained steps
    assert len(sizes) == 4 and sum(sizes) == 2 * stacked, sizes


def test_net_apply_seq_still_takes_uint8_stacks():
    """`benchmarks/harness/sequence_checks.py` rebuilds uint8 stacks and
    calls `learner.net_apply_seq` on them."""
    _, learner, state = _build("frame_rows", "bfloat16")
    items, _ = _draw(learner, state)
    state0 = (items["init_c"], items["init_h"])
    stacks = _parent_batch(items).obs
    prepared = jax.eval_shape(
        lambda it: learner.family.make_batch(it).obs, items)
    assert stacks.dtype == jnp.uint8 and prepared.dtype == jnp.bfloat16
    assert prepared.shape == stacks.shape
    q_u8, s_u8 = jax.jit(learner.net_apply_seq)(state.params, stacks,
                                                state0)
    q_pre, s_pre = jax.jit(lambda p, it: learner.net_apply_seq(
        p, learner.family.make_batch(it).obs, state0))(state.params, items)
    np.testing.assert_array_equal(np.asarray(q_u8), np.asarray(q_pre))
    for a, b in zip(s_u8, s_pre):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sample_k_still_returns_the_stored_uint8_items():
    _, learner, state = _build("frame_rows", "bfloat16")
    sample, _ = learner.sample_k(state, 1)
    items_k, idx_k, _, _ = sample
    # the frames as bytes, and the word rows they were gathered as
    assert set(items_k) == {"seq_frames", "seq_frames" + WORDS, "actions",
                            "rewards", "terminals", "mask", "init_c",
                            "init_h"}
    assert items_k["seq_frames" + WORDS].dtype == jnp.uint32
    assert items_k["seq_frames"].dtype == jnp.uint8
    h, w = LAYOUTS["frame_rows"]["hw"]
    assert items_k["seq_frames"].shape == (1, B, L + STACK - 1, h, w)
    stored = _items(np.random.default_rng(4), LAYOUTS["frame_rows"],
                    FILLED)["seq_frames"]
    np.testing.assert_array_equal(np.asarray(items_k["seq_frames"][0]),
                                  stored[np.asarray(idx_k[0])])


def test_dist_sequence_learner_takes_the_same_prepared_batch():
    _, single, s_state = _build("frame_rows", "bfloat16")
    _, dist, d_state = _build("frame_rows", "bfloat16", dist=True)
    items, w = _draw(single, s_state)
    out = jax.jit(lambda st, it, w: single._sgd_step(
        st.params, st.target_params, st.opt_state, jnp.int32(0), it, w))(
        s_state, items, w)
    # the dist learner's items are [dp, b_local, ...]; it max-normalises
    # the raw weights itself, as the single-chip sample stage has
    d_out = jax.jit(lambda st, it, w: dist._sgd_step(
        st.params, st.target_params, st.opt_state, jnp.int32(0), it, w))(
        d_state, jax.tree.map(lambda x: x[None], items), w[None])
    np.testing.assert_allclose(float(d_out[5]["loss"]),
                               float(out[5]["loss"]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(d_out[4][0]),
                               np.asarray(out[4]), rtol=1e-6)
    batch = dist.family.make_batch(items)
    assert batch.obs.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(batch.obs, np.float32),
        np.asarray(single.family.make_batch(items).obs, np.float32))


# -- (v) the rebuild on word rows -----------------------------------------


def _sampled(frames, frame_dtype=np.uint8, stack=STACK):
    """Items as a sample of the packed store hands them on: `frames`
    [B, n, H, W] through the store's codec, with the fields the batch
    needs beside them."""
    bsz, n = frames.shape[:2]
    length = n - stack + 1
    spec = {"seq_frames": jax.ShapeDtypeStruct(frames.shape[1:],
                                               frame_dtype)}
    packer = PixelPacker(spec)
    rows = packer.rows_per_item()["seq_frames"]
    stored = packer.encode({"seq_frames": jnp.asarray(frames)})
    if rows > 1:
        stored = {"seq_frames": stored["seq_frames"].reshape(
            bsz, rows, -1)}
    z = jnp.zeros((bsz, length), jnp.float32)
    return {**packer.decode(stored, words=True),
            "actions": z.astype(jnp.int32), "rewards": z, "terminals": z,
            "mask": z, "init_c": z[:, :1], "init_h": z[:, :1]}


def _plain(frames, length, stack, compute_dtype):
    obs = jnp.stack([jnp.asarray(frames)[:, c:c + length]
                     for c in range(stack)], axis=-1)
    return obs if compute_dtype is None else preprocess_obs(
        obs, dtype_of(compute_dtype))


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from _primitives(sub)


# the cell's own geometry (80 steps of 84 x 84, cut at 40), a cut at
# an odd step, none; and frames whose lines are not whole words
@pytest.mark.parametrize("compute_dtype", [None, "float32", "bfloat16"])
@pytest.mark.parametrize("hw,length,burn_in", [
    ((84, 84), 80, 40), ((84, 84), 80, 27), ((84, 84), 80, 0),
    ((61, 62), 12, 5)])
def test_word_rows_give_the_plain_stacks_element_for_element(
        hw, length, burn_in, compute_dtype):
    rng = np.random.default_rng(5)
    frames = rng.integers(0, 256, (2, length + STACK - 1, *hw),
                          dtype=np.uint8)
    items = _sampled(frames)
    assert items["seq_frames" + WORDS].dtype == jnp.uint32   # split rows
    dt = None if compute_dtype is None else dtype_of(compute_dtype)
    make = jax.jit(lambda it: batch_to_sequence_batch(it, dt, burn_in).obs)
    got = make(items)
    want = jax.jit(lambda f: _plain(f, length, STACK, compute_dtype))(frames)
    assert got.shape == (2, length, *hw, STACK) and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    # built on whole words: no byte is widened, the bytes of
    # `seq_frames` are never made, one bitcast a side of the cut
    names = [(e.primitive.name, e.invars[0].aval.dtype,
              e.outvars[0].aval.dtype)
             for e in _primitives(jax.make_jaxpr(make)(items).jaxpr)]
    assert ("convert_element_type", jnp.uint8, jnp.uint32) not in names
    casts = [n for n in names if n[0] == "bitcast_convert_type"]
    assert casts == [("bitcast_convert_type", jnp.uint32, jnp.uint8)] * (
        2 if 0 < burn_in < length else 1)


@pytest.mark.parametrize("case", ["stack_of_3", "float32_frames",
                                  "one_row_sequence", "bytes_alone"])
def test_every_other_depth_dtype_and_form_takes_the_plain_path(case):
    """A stack that is not four bytes of a word, frames that are not
    bytes (the packer leaves them alone), a sequence stored as ONE row
    and frames that came without their word rows: four `stack`ed
    slices, equal to the plain form all the same."""
    rng = np.random.default_rng(6)
    length, stack, hw, dtype = 12, STACK, (60, 60), np.uint8
    if case == "stack_of_3":
        stack = 3
    if case == "one_row_sequence":
        hw = (36, 36)
    frames = rng.integers(0, 256, (2, length + stack - 1, *hw),
                          dtype=np.uint8)
    if case == "float32_frames":
        frames, dtype = frames.astype(np.float32) / 7, np.float32
    items = _sampled(frames, dtype, stack)
    if case == "bytes_alone":
        del items["seq_frames" + WORDS]
    assert ("seq_frames" + WORDS in items) == (case == "stack_of_3")
    make = jax.jit(lambda it: batch_to_sequence_batch(
        it, jnp.bfloat16, 5).obs)
    np.testing.assert_array_equal(
        np.asarray(make(items), np.float32),
        np.asarray(jax.jit(lambda f: _plain(f, length, stack, "bfloat16"))(
            frames), np.float32))
    names = [e.primitive.name
             for e in _primitives(jax.make_jaxpr(make)(items).jaxpr)]
    assert "shift_left" not in names and "concatenate" in names
