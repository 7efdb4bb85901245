"""The two one-nest routed decoders' loss reads the head by column
(ISSUE 49): the target net's bootstrap and the online net's Q(s, a) are
one column a token (models/q_head.py, ops/losses.column_read), and of a
step's four [tokens, hidden] x [hidden, A] products one is left. The
family gives the column read to a net that offers `head_at` and the
dense read to one that does not (runtime/family.decoder_q_family).

(a) the family's loss is the DENSE form (built here from `net.apply`
    and `make_r2d2_loss`) in the loss, every aux key and every gradient
    leaf: in float32 to rounding, in bfloat16 within the dense form's
    own error against float32; with and without double-Q;
(b) `q_at` is the matmul's column, and an id's repeats add up in
    float32 where a bfloat16 sum would stall;
(c) the mechanism's counter: the gradient program of the family's loss
    has one dot over the vocabulary held and none that reads a float32
    [tokens, A] operand, for the two nets that offer the read, and the
    dense form's two and two for the three that do not;
(d) `head_columns` reads 2 x trained tokens, and is absent (not 0) for
    a net on the dense read.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.configs import get_config
from ape_x_dqn_tpu.models import build_network, decoder_block
from ape_x_dqn_tpu.models.q_head import q_at
from ape_x_dqn_tpu.ops.losses import make_r2d2_loss
from ape_x_dqn_tpu.runtime.family import learner_family, reads_by_column

BY_COLUMN = ("trinity_tiny_q", "smallthinker_tiny_q")
DENSE = ("glm_tiny_q", "ouro_tiny_q", "kimi_linear_tiny_q")


def _decoder_case(preset, burn_in=None, double=True, dtype="float32",
                  held=None, seed=0):
    """-> (cfg, net, the family, params, target params, a batch whose
    action ids REPEAT inside the batch and whose tail is padding, IS
    weights): what the two forms of the loss below are held on.
    `held`: the vocabulary rows the net holds, where the preset's own
    count is also one of its widths."""
    cfg = get_config(preset)
    network = dataclasses.replace(cfg.network, compute_dtype=dtype)
    if held is not None:
        name, block = decoder_block(network)
        ways = block.vocab_size // build_network(network, None).num_actions
        network = dataclasses.replace(network, **{name: dataclasses.replace(
            block, vocab_size=held * ways)})
    cfg = dataclasses.replace(
        cfg, network=network,
        learner=dataclasses.replace(cfg.learner, double_dqn=double))
    if burn_in is not None:
        cfg = dataclasses.replace(
            cfg, replay=dataclasses.replace(cfg.replay, burn_in=burn_in))
    net = build_network(cfg.network, None)
    family = learner_family(cfg, net)
    length, n = cfg.replay.seq_length, cfg.learner.batch_size
    rng = np.random.default_rng(seed)
    mask = np.ones((n, length), np.float32)
    mask[0, -3:] = 0.0
    terminals = np.zeros((n, length), np.float32)
    terminals[-1, length - 4] = 1.0
    batch = family.make_batch({
        "obs": rng.integers(0, net.num_actions, (n, length)).astype(np.int32),
        # three ids for the whole batch: every id many times over
        "actions": rng.integers(0, 3, (n, length)).astype(np.int32),
        "rewards": rng.normal(size=(n, length)).astype(np.float32),
        "terminals": terminals, "mask": mask})
    params = net.init(jax.random.key(seed))
    target = net.init(jax.random.key(seed + 1))
    weights = jnp.asarray(rng.random(n) + 0.5, jnp.float32)
    return cfg, net, family, params, target, batch, weights


def _dense_form(cfg, net):
    """The sequence loss over the net's whole Q arrays, built here from
    `net.apply` and `make_r2d2_loss`'s default read as the family has it
    for a net without `head_at`: four full heads, two float32 [tokens,
    A] arrays and the gradient of one."""
    lcfg, rcfg = cfg.learner, cfg.replay
    return make_r2d2_loss(
        net.apply, burn_in=rcfg.burn_in, n_step=lcfg.n_step,
        gamma=lcfg.gamma, huber_delta=lcfg.huber_delta,
        double=lcfg.double_dqn, rescale=lcfg.value_rescale,
        priority_eta=rcfg.priority_eta)


def _both_forms(preset, burn_in, double, dtype):
    """-> ((loss, aux), grads) of the family's loss and of the dense
    form, on the same batch."""
    cfg, net, family, params, target, batch, weights = _decoder_case(
        preset, burn_in, double, dtype)
    assert reads_by_column(net)
    got = jax.jit(jax.value_and_grad(family.loss_fn, has_aux=True))(
        params, target, batch, weights)
    want = jax.jit(jax.value_and_grad(_dense_form(cfg, net), has_aux=True))(
        params, target, batch, weights)
    return got, want


def _distance(tree, ref):
    """Per leaf, |leaf - ref| / |ref| in the 2-norm."""
    return [float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))
            for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(ref))]


def _held_to_the_dense_form_in_float32(preset, burn_in, double):
    ((loss, aux), grads), ((want, want_aux), want_grads) = _both_forms(
        preset, burn_in, double, "float32")
    # the same sum over `hidden` in another order: rounding, no more
    tol = dict(rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(loss, want, **tol)
    assert set(want_aux) <= set(aux)
    for key, value in want_aux.items():
        np.testing.assert_allclose(aux[key], value, err_msg=key, **tol)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    want_flat = jax.tree.leaves(want_grads)
    assert len(flat) == len(want_flat)
    moved = 0
    for (path, got), ref in zip(flat, want_flat):
        scale = float(jnp.abs(ref).max())
        moved += scale > 0
        np.testing.assert_allclose(
            got, ref, rtol=0, atol=2e-5 * scale + 1e-12,
            err_msg=jax.tree_util.keystr(path))
    assert moved > len(flat) // 2
    # the head's gradient sits in the columns of the ids taken, summed
    # over their repeats, and nowhere else
    head = np.asarray(grads["lm_head"])
    assert np.abs(head[:, :3]).max() > 0 and not head[:, 3:].any()
    return aux


@pytest.mark.parametrize("burn_in", [None, 0], ids=["own_prefix", "no_prefix"])
@pytest.mark.parametrize("preset", BY_COLUMN)
def test_loss_by_column_is_the_dense_loss_in_float32(preset, burn_in):
    """The loss, the priorities, every other aux key and every gradient
    leaf, with ids that repeat inside the batch, padding and a terminal,
    after the preset's own burn-in and after none."""
    aux = _held_to_the_dense_form_in_float32(preset, burn_in, True)
    assert "q" in aux and aux["q"].ndim == 3


@pytest.mark.parametrize("preset", BY_COLUMN)
def test_loss_by_column_in_bfloat16_is_inside_the_dense_forms_own_error(
        preset):
    """bfloat16 compute: what differs from the dense form is the order
    of a float32 sum over `hidden` and that the head's cotangent is NOT
    rounded on its way, so against the float32 loss the column read is
    no further off than the dense form is, leaf by leaf."""
    (_, ref_grads) = _both_forms(preset, None, True, "float32")[1]
    ((loss, aux), grads), ((want, want_aux), want_grads) = _both_forms(
        preset, None, True, "bfloat16")
    np.testing.assert_allclose(loss, want, rtol=2e-2)
    np.testing.assert_allclose(aux["td_abs"], want_aux["td_abs"],
                               rtol=2e-2, atol=2e-3)
    own = _distance(want_grads, ref_grads)
    ours = _distance(grads, ref_grads)
    assert max(own) > 1e-3      # bfloat16 did round something
    for path, a, b in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                          ours, own):
        assert a <= 1.1 * b + 1e-6, (jax.tree_util.keystr(path[0]), a, b)


@pytest.mark.parametrize("preset", BY_COLUMN)
def test_without_double_q_the_targets_whole_slice_stays(preset):
    """`double_dqn=False` needs a max over the target's whole slice:
    that path keeps the full target head beside the online net's (two
    dots over the vocabulary, none reads a cotangent; one column read a
    token), and the numbers still agree."""
    aux = _held_to_the_dense_form_in_float32(preset, None, False)
    cfg, net, family, params, target, batch, weights = _decoder_case(
        preset, double=False, held=88)
    assert _vocabulary_dots(family.loss_fn, 88, params, target, batch,
                            weights) == (2, 0)
    trained = cfg.learner.batch_size * (
        cfg.replay.seq_length - cfg.replay.burn_in)
    assert float(aux["head_columns"]) == trained


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("held", [96, 128], ids=["96_ids", "128_ids"])
def test_the_column_read_is_the_matmuls_column(held, dtype):
    """`q_at` against the head's matmul and its gradient against the
    matmul's, at a vocabulary that is whole lane tiles and at one that
    is not; ids repeat and the repeats add up."""
    hidden, dt = 24, jnp.dtype(dtype)
    kx, kw, ki, kg = jax.random.split(jax.random.key(held), 4)
    x = jax.random.normal(kx, (2, 9, hidden), jnp.float32).astype(dt)
    w = jax.random.normal(kw, (hidden, held), jnp.float32)
    ids = jax.random.randint(ki, (2, 9), 0, 4)
    g = jax.random.normal(kg, (2, 9), jnp.float32)

    def dense(x, w):
        q = jnp.dot(x, w.astype(x.dtype), preferred_element_type=jnp.float32)
        return jnp.take_along_axis(q, ids[..., None], -1)[..., 0]

    got, pull = jax.vjp(lambda x, w: q_at(x, w, ids), x, w)
    want, pull_dense = jax.vjp(dense, x, w)
    exact = dt == jnp.float32
    tol = dict(rtol=1e-5, atol=1e-6) if exact else dict(rtol=2e-2, atol=2e-2)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, **tol)
    (d_x, d_w), (want_x, want_w) = jax.jit(pull)(g), pull_dense(g)
    assert d_x.dtype == dt and d_w.dtype == jnp.float32
    np.testing.assert_allclose(d_x.astype(jnp.float32),
                               want_x.astype(jnp.float32), **tol)
    np.testing.assert_allclose(d_w, want_w, **tol)
    assert not np.asarray(d_w)[:, 4:].any()


@pytest.mark.parametrize("preset", BY_COLUMN)
def test_an_ids_repeats_accumulate_in_float32(preset):
    """1,024 tokens all take id 5, each adds exactly 1 to every entry
    of that column's gradient (x = 1, g = 1): a float32 sum reads 1,024,
    a bfloat16 sum stalls at 256 (256 + 1 rounds back to 256), which is
    where autodiff's transpose of a gather of ROUNDED columns would add.
    The columns read are the rounded ones: a weight of 1 + 2^-10 reads
    as 1."""
    cfg = get_config(preset)
    net = build_network(dataclasses.replace(
        cfg.network, compute_dtype="bfloat16"), None)
    params = net.init(jax.random.key(0))
    hidden = params["lm_head"].shape[0]
    params = {**params, "lm_head": jnp.full_like(
        params["lm_head"], 1.0 + 2.0 ** -10)}
    x = jnp.ones((4, 256, hidden), jnp.bfloat16)
    ids = jnp.full((4, 256), 5, jnp.int32)
    q, pull = jax.vjp(lambda p: net.head_at(p, x, ids), params)
    np.testing.assert_array_equal(q, np.full((4, 256), hidden, np.float32))
    (grads,) = jax.jit(pull)(jnp.ones((4, 256), jnp.float32))
    head = np.asarray(grads["lm_head"])
    assert head.dtype == np.float32
    np.testing.assert_array_equal(head[:, 5], np.full(hidden, 1024.0))
    assert not np.delete(head, 5, axis=1).any()
    stalled = jnp.zeros((), jnp.bfloat16)
    for _ in range(1024):
        stalled = stalled + jnp.ones((), jnp.bfloat16)
    assert float(stalled) == 256.0


def _dots(jaxpr, found):
    """Every `dot_general` of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _dots(sub, found)
    return found


def _vocabulary_dots(loss_fn, a, params, target, batch, weights):
    """-> (dots whose result's last axis is the vocabulary held `a`,
    dots that read a float32 [B, T, a] operand: the head's cotangent)
    in what a train step differentiates, with what its results do not
    depend on removed."""
    from jax._src.interpreters import partial_eval as pe

    def step(params, target):
        # what the learner keeps of a step: loss, priorities, grads and
        # the health scalars (obs/learning.sgd_diag reads q_max, q_gap)
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, target, batch, weights)
        return loss, aux["td_abs"], aux["q_max"], aux["q_gap"], grads

    closed = jax.make_jaxpr(step)(params, target)
    jaxpr, _ = pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.out_avals))
    dots = _dots(jaxpr, [])
    over = [d for d in dots if d.outvars[0].aval.shape[-1:] == (a,)]
    reads = [d for d in dots for v in d.invars
             if v.aval.dtype == jnp.float32 and v.aval.shape[-1:] == (a,)
             and v.aval.ndim == 3]
    return len(over), len(reads)


@pytest.mark.parametrize("preset", BY_COLUMN + DENSE)
def test_a_train_step_has_one_dot_over_the_vocabulary_where_the_net_offers(
        preset):
    """The mechanism's counter (ISSUE 49): in the gradient program of
    the family's loss ONE dot has a result whose last axis is the
    vocabulary held (the online net's Q for the argmax) and no dot
    reads a float32 [B, T, A] operand (the head's cotangent), for the
    two nets that offer `head_at`. The dense form has two and two, its
    four head products, and so have the three nets that keep it
    (GlmMoeQNet, OuroQNet, KimiLinearQNet: ISSUE 49 says why each). A
    later PR that brings a dense head back to the first two, or moves
    one of the three, fails here."""
    a = 88     # no tiny net has a width of 88
    cfg, net, family, params, target, batch, weights = _decoder_case(
        preset, held=a)
    assert net.num_actions == a
    assert [leaf.shape for leaf in jax.tree.leaves(params)
            if a in leaf.shape] == [(a, params["lm_head"].shape[0]),
                                    params["lm_head"].shape]
    assert _vocabulary_dots(family.loss_fn, a, params, target, batch,
                            weights) == ((1, 0) if preset in BY_COLUMN
                                         else (2, 2))
    assert _vocabulary_dots(_dense_form(cfg, net), a, params, target, batch,
                            weights) == (2, 2)


@pytest.mark.parametrize("preset", BY_COLUMN + DENSE)
def test_head_columns_reads_twice_the_trained_tokens_or_is_absent(preset):
    """`head_columns`, the columns read a step (online + target): 2 x
    trained tokens exactly under double-Q for a net that reads by
    column, among its metric keys; for a net on the dense read it is
    ABSENT from the aux and the metric keys, not 0."""
    cfg, net, family, params, target, batch, weights = _decoder_case(preset)
    assert reads_by_column(net) == (preset in BY_COLUMN)
    _, aux = jax.jit(family.loss_fn)(params, target, batch, weights)
    if preset in BY_COLUMN:
        trained = cfg.learner.batch_size * (
            cfg.replay.seq_length - cfg.replay.burn_in)
        assert float(aux["head_columns"]) == 2 * trained
        assert "head_columns" in family.metric_keys
    else:
        assert "head_columns" not in aux
        assert "head_columns" not in family.metric_keys
        assert not hasattr(net, "head_at")
