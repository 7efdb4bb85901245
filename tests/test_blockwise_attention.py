"""ops/blockwise_attention.py against dense masked attention: both
masks (causal; causal inside a window), forward and every gradient,
with and without a cache of earlier positions, in blocks of 4 so that
the diagonal, the window's edge, the front padding and the cache
boundary each fall inside a tile somewhere."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.ops.blockwise_attention import blockwise_attention


def dense_attention(q, k, v, cache, window):
    t, group = q.shape[1], q.shape[2] // k.shape[2]
    if cache is not None:
        k = jnp.concatenate([jax.lax.stop_gradient(cache[0]), k], 1)
        v = jnp.concatenate([jax.lax.stop_gradient(cache[1]), v], 1)
    first = k.shape[1] - t
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(q.shape[-1])
    apart = (first + jnp.arange(t))[:, None] - jnp.arange(k.shape[1])[None]
    vis = (apart >= 0) if window is None else (apart >= 0) & (apart < window)
    probs = jax.nn.softmax(jnp.where(vis, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


@pytest.mark.parametrize("t,cached,window", [
    (20, 0, None), (20, 12, None),      # full: segment alone, after a prefix
    (12, 0, 8), (20, 7, 8),             # sliding: prefix, after a trimmed one
    (20, 12, 8),                        # a cache longer than the window - 1
    (16, 5, 3)])                        # key padding and a window < a block
@pytest.mark.parametrize("recompute_delta", [False, True])
def test_blockwise_attention_equals_dense_masked(t, cached, window,
                                                 recompute_delta):
    """Forward and every gradient, both masks, with and without a
    cache, the backward pass with delta from the output and from its
    own first walk of the key blocks; blocks of 4 so that diagonal,
    window edge, padding and the cache boundary each fall inside a tile
    somewhere."""
    rng = np.random.default_rng(t + cached)
    new = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa
    q, k, v = new(2, t, 4, 16), new(2, t, 2, 16), new(2, t, 2, 16)
    cache = (new(2, cached, 2, 16), new(2, cached, 2, 16)) if cached else None
    weight = new(2, t, 4, 16)
    blockwise = lambda *a: blockwise_attention(            # noqa: E731
        *a, window=window, block_q=4, block_k=4,
        recompute_delta=recompute_delta)
    np.testing.assert_allclose(blockwise(q, k, v, cache),
                               dense_attention(q, k, v, cache, window),
                               atol=1e-5)
    got = jax.jit(jax.grad(lambda *a: (blockwise(*a) * weight).sum(),
                           (0, 1, 2)))(q, k, v, cache)
    want = jax.grad(lambda *a: (dense_attention(*a, window) * weight).sum(),
                    (0, 1, 2))(q, k, v, cache)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5)
    if cache is not None:       # the cache carries no gradient
        g = jax.grad(lambda c: (blockwise(q, k, v, c) * weight).sum())(cache)
        assert not np.any(g[0]) and not np.any(g[1])


def test_no_array_of_queries_by_keys_exists():
    """The jaxpr of a call at T = S = 64 in blocks of 8 holds no array
    with two dimensions of 64."""
    x = jnp.zeros((1, 64, 2, 8))
    jaxpr = jax.make_jaxpr(jax.grad(lambda q: blockwise_attention(
        q, x[:, :, :1], x[:, :, :1], block_q=8, block_k=8).sum()))(x)

    def shapes(j):
        for eqn in j.eqns:
            for var in eqn.outvars:
                yield var.aval.shape
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    assert all(list(s).count(64) < 2 for s in shapes(jaxpr.jaxpr))


# `smallthinker_offline`'s geometry at 1/128 of its lengths (ISSUE 39):
# 7 query heads to a key head (28 / 4), a window of 8 key blocks, a
# prefix as long as the window, 32 key blocks in all. Nothing in
# ops/blockwise_attention.py had to change for it: these cases say so.
@pytest.mark.parametrize("t,cached,window", [
    (32, 0, None),       # the global layer's prefix pass: 8 blocks
    (96, 32, None),      # its trained segment over the whole prefix
    (32, 0, 32),         # a sliding layer's prefix: the window = the prefix
    (96, 31, 32),        # its trained segment after the trimmed cache
    (96, 32, 32)])       # ... and after the untrimmed one: the same values
def test_a_group_of_seven_and_thirty_two_key_blocks(t, cached, window):
    rng = np.random.default_rng(7 * t + cached)
    new = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa
    q, k, v = new(1, t, 14, 8), new(1, t, 2, 8), new(1, t, 2, 8)
    cache = (new(1, cached, 2, 8), new(1, cached, 2, 8)) if cached else None
    weight = new(1, t, 14, 8)
    blockwise = lambda *a: blockwise_attention(            # noqa: E731
        *a, window=window, block_q=4, block_k=4)
    if cached:          # with its cache: 32 key blocks of 4, as the cell's
        assert -(-(cached + t) // 4) == 32
    np.testing.assert_allclose(blockwise(q, k, v, cache),
                               dense_attention(q, k, v, cache, window),
                               atol=1e-5)
    got = jax.jit(jax.grad(lambda *a: (blockwise(*a) * weight).sum(),
                           (0, 1, 2)))(q, k, v, cache)
    want = jax.grad(lambda *a: (dense_attention(*a, window) * weight).sum(),
                    (0, 1, 2))(q, k, v, cache)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5)


# `ouro_offline`'s geometry at 1/128 of its lengths (ISSUE 41): a GROUP
# OF ONE (16 query heads on 16 key-value heads; 4 on 4 here) at the
# model's head size of 128, every layer a full one, a prefix of 8 =
# 1,024 / 128 positions and 24 trained, 8 key blocks of 4 in all - plain,
# with the `recompute_delta` that OuroQNet asks for and with SmallThinker's
# `about_mean` (float32: no op). The shapes needed nothing of
# ops/blockwise_attention.py; the chip's check asked for the argument.
@pytest.mark.parametrize("how", [{}, {"recompute_delta": True},
                                 {"about_mean": True}],
                         ids=["plain", "recompute_delta", "about_mean"])
@pytest.mark.parametrize("t,cached", [
    (8, 0),        # the prefix pass
    (24, 8)])      # the trained segment over the prefix's cache
def test_a_group_of_one_at_head_size_128_with_a_cache(t, cached, how):
    rng = np.random.default_rng(11 * t + cached)
    new = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa
    q, k, v = new(2, t, 4, 128), new(2, t, 4, 128), new(2, t, 4, 128)
    cache = (new(2, cached, 4, 128), new(2, cached, 4, 128)) if cached \
        else None
    weight = new(2, t, 4, 128)
    blockwise = lambda *a: blockwise_attention(            # noqa: E731
        *a, window=None, block_q=4, block_k=4, **how)
    if cached:
        assert (cached + t) // 4 == 8
    np.testing.assert_allclose(blockwise(q, k, v, cache),
                               dense_attention(q, k, v, cache, None),
                               atol=1e-5)
    got = jax.jit(jax.grad(lambda *a: (blockwise(*a) * weight).sum(),
                           (0, 1, 2)))(q, k, v, cache)
    want = jax.grad(lambda *a: (dense_attention(*a, None) * weight).sum(),
                    (0, 1, 2))(q, k, v, cache)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_a_sliding_layer_visits_the_blocks_its_window_admits_and_no_more():
    """At the cell's proportions (window = 8 blocks, 32 key blocks) a
    query block of a sliding layer visits 9 key blocks and the last
    block of a global layer all 32: what the window saves is in the
    loop bounds, at a group of 7 as at 8."""
    from ape_x_dqn_tpu.ops.blockwise_attention import _bounds, _Geometry

    sliding = _Geometry(pad=1, first=32, window=32, block_q=4, block_k=4)
    full = sliding._replace(pad=0, window=None)
    visits = [int(hi) - int(lo) for lo, hi in
              (_bounds(sliding, i) for i in range(24))]
    assert set(visits) == {9}
    lo, hi = _bounds(full, 23)
    assert (int(lo), int(hi)) == (0, 32)


def _errors_on_near_equal_keys_and_values(**how) -> list[float]:
    """bfloat16, keys, values and the output's cotangent each one common
    vector plus 1% of noise (what a decoder without q/k norms has after
    a few optimizer steps): the relative errors of the queries', keys'
    and values' gradients against dense float32 attention on the same
    rounded inputs. `how`: the call's way with such rows (by default
    `about_mean`)."""
    from ape_x_dqn_tpu.ops import blockwise_attention as ba

    rng = np.random.default_rng(0)
    t, d = 512, 16
    near = lambda *s: (rng.normal(size=(1, 1, s[2], d))        # noqa: E731
                       + 0.01 * rng.normal(size=s))
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)                # noqa: E731
    f32 = lambda a: a.astype(jnp.float32)                      # noqa: E731
    q = bf(rng.normal(size=(1, t, 2, d)))
    k, v, weight = (bf(near(1, t, 1, d)), bf(near(1, t, 1, d)),
                    bf(near(1, t, 2, d)))
    got = jax.grad(lambda *a: (f32(ba.blockwise_attention(
        *a, block_q=64, block_k=64, **(how or {"about_mean": True})))
        * f32(weight)).sum(),
        (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (dense_attention(
        *a, None, None) * f32(weight)).sum(), (0, 1, 2))(
        f32(q), f32(k), f32(v))
    return [float(jnp.linalg.norm(f32(a) - b) / jnp.linalg.norm(b))
            for a, b in zip(got, want)]


def test_keys_and_values_go_in_less_their_mean_below_float32(monkeypatch):
    """`about_mean` (ISSUE 39): attention does not see a vector
    added to every key and gives back one added to every value, and
    without the common vector the row sum the backward pass leaves in ds
    (its weights against the forward pass's rounded ones) has nothing to
    multiply. Read here: the queries' gradient 5.0 of its norm without,
    0.005 with; the keys' 0.013 / 0.003; the values' 0.002 either way."""
    from ape_x_dqn_tpu.ops import blockwise_attention as ba

    dq, dk, dv = _errors_on_near_equal_keys_and_values()
    assert dq < 0.02 and dk < 0.01 and dv < 0.01
    monkeypatch.setattr(ba, "_about_its_mean", lambda x: (x, None))
    assert _errors_on_near_equal_keys_and_values()[0] > 2.0


def test_delta_from_the_backward_passes_own_weights():
    """`recompute_delta` (ISSUE 41's check on the chip): a row of ds
    sums to zero whatever the forward pass's weights were, so the row
    sum has nothing to leave on what keys and values share. Read here,
    on the same near-equal rows: the queries' gradient 5.0 of its norm
    with delta from the output, 0.14 with the first walk (what is left
    is the rounding of ds itself against the keys' common vector, which
    the autodiff of a materialised bfloat16 softmax has too and
    `about_mean` takes away); keys and values as with `about_mean`."""
    dq, dk, dv = _errors_on_near_equal_keys_and_values(recompute_delta=True)
    assert dq < 0.3 and dk < 0.01 and dv < 0.01
    assert _errors_on_near_equal_keys_and_values(about_mean=False)[0] > 2.0


def test_the_first_walk_is_two_tile_products_and_an_exp():
    """What `recompute_delta` adds to the backward pass, and that the
    plain pass holds none of it."""
    x = jnp.ones((1, 16, 2, 4), jnp.float32)

    def ops(flag):
        text = str(jax.make_jaxpr(jax.grad(
            lambda q, k, v: blockwise_attention(
                q, k, v, block_q=4, block_k=4,
                recompute_delta=flag).sum(), (0, 1, 2)))(x, x, x))
        return text.count("dot_general"), text.count(" exp ")

    (dots, exps), (dots_twice, exps_twice) = ops(False), ops(True)
    assert (dots_twice, exps_twice) == (dots + 2, exps + 1)


def test_float32_attention_takes_no_mean():
    """float32 compute is untouched: no op added, so the tiny presets'
    pinned `train_many` programs do not move."""
    from ape_x_dqn_tpu.ops import blockwise_attention as ba

    x = jnp.ones((1, 8, 2, 4), jnp.float32)
    same, mean = ba._about_its_mean(x)
    assert same is x and mean is None
    def lowered(flag):
        return jax.jit(lambda q, k, v: blockwise_attention(
            q, k, v, block_q=4, block_k=4, about_mean=flag)).lower(
            x, x, x).as_text()

    assert lowered(True) == lowered(False)


def test_what_is_taken_off_comes_back_to_the_last_bit():
    """`_about_its_mean` rounds nothing twice: the mean is taken off as
    a bfloat16 number and only where it is several spreads large, so x
    and it lie within a factor of two and their difference is exact
    (Sterbenz); a coordinate without a common part is left as it is."""
    from ape_x_dqn_tpu.ops.blockwise_attention import _about_its_mean

    rng = np.random.default_rng(3)
    common = np.where(np.arange(16) < 8, rng.normal(size=16) + 3.0, 0.0)
    x = jnp.asarray(common + 0.05 * rng.normal(size=(2, 256, 3, 16)),
                    jnp.bfloat16)
    less, taken = _about_its_mean(x)
    assert less.dtype == jnp.bfloat16 and taken.shape == (2, 3, 16)
    back = less.astype(jnp.float32) + taken[:, None]
    np.testing.assert_array_equal(np.asarray(back),
                                  np.asarray(x.astype(jnp.float32)))
    assert (np.asarray(taken)[..., :8] != 0).all()
    assert (np.asarray(taken)[..., 8:] == 0).all()
    np.testing.assert_array_equal(np.asarray(less[..., 8:]),
                                  np.asarray(x[..., 8:]))
    assert float(jnp.abs(less[..., :8].astype(jnp.float32)).max()) < 0.5


# -- the backward pass's two schedules (ISSUE 44) --------------------------

def _gradients(monkeypatch, two_nests, q, k, v, cache, weight, **how):
    """dq, dk, dv of the call in blocks of 4 with the rule's constant set
    so that the group takes the asked-for schedule."""
    from ape_x_dqn_tpu.ops import blockwise_attention as ba

    group = q.shape[2] // k.shape[2]
    monkeypatch.setattr(ba, "TWO_NESTS_UP_TO_GROUP",
                        group if two_nests else group - 1)
    f32 = lambda a: a.astype(jnp.float32)                      # noqa: E731
    return jax.grad(lambda *a: (f32(ba.blockwise_attention(
        *a, block_q=4, block_k=4, **how)) * f32(weight)).sum(),
        (0, 1, 2))(q, k, v, cache)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("about_mean", [False, True],
                         ids=["plain", "about_mean"])
@pytest.mark.parametrize("recompute_delta", [False, True],
                         ids=["delta_from_out", "recompute_delta"])
@pytest.mark.parametrize("window", [None, 6], ids=["full", "window_6"])
@pytest.mark.parametrize("cached", [0, 8, 5],
                         ids=["no_cache", "whole_blocks", "padded"])
@pytest.mark.parametrize("group", [1, 4])
def test_two_nests_give_the_one_nests_gradients_to_the_last_bit(
        monkeypatch, group, cached, window, recompute_delta, about_mean,
        dtype):
    """A key block's dk and dv summed in the inner loop's carry over its
    query blocks, ascending, are the sums the one nest makes tile visit
    by tile visit; dq's walk is the one nest's without the split at the
    cache's edge. A window of 6 cuts blocks of 4 on both sides; a cache
    of 5 puts 3 zero keys in front and the first key with a gradient in
    the middle of a block; the rows share a common vector so that
    `about_mean` takes one off."""
    rng = np.random.default_rng(100 * group + 10 * cached + (window or 0))
    new = lambda *s: jnp.asarray(                              # noqa: E731
        rng.normal(size=s) + 6.0 * rng.normal(size=s[-1]), dtype)
    t, kv, d = 20, 2, 8
    q, k, v = new(2, t, kv * group, d), new(2, t, kv, d), new(2, t, kv, d)
    cache = (new(2, cached, kv, d), new(2, cached, kv, d)) if cached else None
    weight = new(2, t, kv * group, d)
    how = dict(window=window, recompute_delta=recompute_delta,
               about_mean=about_mean)
    one = _gradients(monkeypatch, False, q, k, v, cache, weight, **how)
    two = _gradients(monkeypatch, True, q, k, v, cache, weight, **how)
    for a, b in zip(one, two):
        assert a.dtype == b.dtype == dtype
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)))
    assert float(jnp.abs(one[1].astype(jnp.float32)).max()) > 0


@pytest.mark.parametrize("window", [None, 1, 3, 4, 6, 9, 64])
@pytest.mark.parametrize("cached", [0, 3, 5, 8, 13])
@pytest.mark.parametrize("blocks", [(4, 4), (2, 4), (4, 2), (8, 4), (3, 5)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
def test_the_dkv_nest_visits_the_tiles_the_bounds_admit(blocks, cached,
                                                        window):
    """`_admitting` is `_bounds` read the other way: over key blocks
    with a gradient, outside, and the query blocks it gives, inside,
    the dk/dv nest visits exactly the (query block, key block) pairs
    the dq nest visits among those key blocks - each key block's query
    blocks one unbroken ascending range."""
    from ape_x_dqn_tpu.ops.blockwise_attention import (
        _admitting, _bounds, _Geometry)

    t, (bq, bk) = 24, blocks
    pad = -(cached + t) % bk
    geo = _Geometry(pad=pad, first=pad + cached, window=window,
                    block_q=bq, block_k=bk)
    query_blocks, key_blocks = t // bq, (pad + cached + t) // bk
    with_grad_from = geo.first // bk
    by_rows = {(i, j) for i in range(query_blocks)
               for j in range(*map(int, _bounds(geo, i)))
               if j >= with_grad_from}
    by_keys = {(i, j) for j in range(with_grad_from, key_blocks)
               for i in range(*map(int, _admitting(geo, j, query_blocks)))}
    assert by_keys == by_rows
    assert {j for _, j in by_keys} == set(range(with_grad_from, key_blocks))


def _inner_loops(jaxpr):
    """Every `while` of a jaxpr that holds no other, innermost first as
    found."""
    for eqn in jaxpr.eqns:
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        inner = [loop for sub in subs for loop in _inner_loops(sub)]
        yield from inner
        if eqn.primitive.name == "while" and not inner:
            yield eqn


def test_the_dkv_nests_inner_loop_is_four_tile_products_and_an_exp(
        monkeypatch):
    """Beside `test_the_first_walk_is_two_tile_products_and_an_exp`: a
    tile visit of the dk/dv nest makes scores and dp again, then dk's
    and dv's products - four `dot_general`s, one `exp` - and what the
    loop carries is its counter, its bound and TWO TILES [B, KV,
    block_k, d]: no array of the keys' whole length (28 here, where the
    queries are 16) is written on a visit. The three inner loops of the
    backward pass are the first walk (2 products), dq's (3) and this
    one; the one-nest schedule has none of four products without an
    accumulator of the keys' length in its carry."""
    from ape_x_dqn_tpu.ops import blockwise_attention as ba

    x = jnp.ones((1, 16, 2, 8), jnp.float32)
    cache = (jnp.ones((1, 12, 2, 8)), jnp.ones((1, 12, 2, 8)))

    def loops(upto):
        monkeypatch.setattr(ba, "TWO_NESTS_UP_TO_GROUP", upto)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q, k, v: ba.blockwise_attention(
                q, k, v, cache, block_q=4, block_k=4,
                recompute_delta=True).sum(), (0, 1, 2)))(x, x, x)
        found = []
        for loop in _inner_loops(jaxpr.jaxpr):
            body = str(loop.params["body_jaxpr"])
            found.append((body.count("dot_general"), body.count(" exp "),
                          [v.aval.shape for v in loop.outvars]))
        return found

    two = [f for f in loops(1) if f[0] >= 2][-3:]        # the backward's
    assert [(dots, exps) for dots, exps, _ in two] == [(2, 1), (3, 1), (4, 1)]
    carried = two[-1][2]
    assert sorted(s for s in carried if s) == [(1, 2, 4, 8), (1, 2, 4, 8)]
    assert not any(28 in s for _, _, shapes in two for s in shapes)
    one = [f for f in loops(0) if f[0] >= 2][-3:]
    assert [(dots, exps) for dots, exps, _ in one] == [(2, 1), (3, 1), (5, 1)]
    assert [s for s in one[-1][2] if 28 in s] == [(1, 2, 28, 8), (1, 2, 28, 8)]


@pytest.mark.parametrize("cell,heads,kv,how,two_nests", [
    ("ouro_offline", 16, 16, {"recompute_delta": True}, True),
    ("smallthinker_offline", 28, 4, {"about_mean": True}, False),
    ("trinity_mini_offline", 32, 4, {}, False)])
def test_the_rule_puts_the_cells_shapes_where_the_docstring_says(
        cell, heads, kv, how, two_nests):
    """The schedule is chosen from the call's shapes alone: a group of
    one (`ouro_offline`: 16 query heads on 16) takes two nests and opens
    their scopes inside the caller's; groups of 7 and 8 keep the one
    nest and no op of theirs carries the names. Lowered at blocks of
    512 and head size 128, nothing compiled."""
    from ape_x_dqn_tpu.ops import blockwise_attention as ba

    assert ba.TWO_NESTS_UP_TO_GROUP == 1
    q = jax.ShapeDtypeStruct((1, 1024, heads, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 1024, kv, 128), jnp.bfloat16)
    c = jax.ShapeDtypeStruct((1, 512, kv, 128), jnp.bfloat16)

    def loss(q, k, v, kc, vc):
        with jax.named_scope("afmoe.attn.full"):
            return ba.blockwise_attention(
                q, k, v, (kc, vc), **how).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, k, k, c, c).as_text(
        debug_info=True)
    stacks = set(re.findall(r'loc\("([^"]+)"', text))
    for scope in (ba.DQ_SCOPE, ba.DKV_SCOPE):
        under = [s for s in stacks if scope in s]
        assert bool(under) == two_nests, (cell, scope)
        # every reader that sums under the caller's scope still sees them
        assert all("afmoe.attn.full" in s.split(scope)[0] for s in under)


@pytest.mark.parametrize("recompute_delta", [False, True])
@pytest.mark.parametrize("group,schedule", [(1, "two_nests"),
                                            (4, "one_nest")])
@pytest.mark.parametrize("t,cached", [(20, 0), (20, 12), (16, 5)])
def test_a_value_head_size_other_than_the_keys(t, cached, group, schedule,
                                               recompute_delta):
    """Multi-head latent attention's shapes (models/mla.py): keys of 24,
    values of 16; the output and dv take the values' size, dq and dk the
    keys', forward and every gradient against a materialised softmax,
    under both backward schedules (a group of one runs two nests)."""
    rng = np.random.default_rng(t + cached + group)
    new = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa
    kv = 2
    q, k, v = new(2, t, kv * group, 24), new(2, t, kv, 24), new(2, t, kv, 16)
    cache = (new(2, cached, kv, 24), new(2, cached, kv, 16)) if cached else None
    weight = new(2, t, kv * group, 16)
    blockwise = lambda *a: blockwise_attention(            # noqa: E731
        *a, block_q=4, block_k=4, recompute_delta=recompute_delta)
    out = blockwise(q, k, v, cache)
    assert out.shape == (2, t, kv * group, 16)
    np.testing.assert_allclose(out, dense_attention(q, k, v, cache, None),
                               atol=1e-5)
    loss = lambda *a: (blockwise(*a) * weight).sum()       # noqa: E731
    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        q, k, v, cache).as_text(debug_info=True)
    assert ("attn.bwd.dkv" in text) == (schedule == "two_nests")
    got = jax.jit(jax.grad(loss, (0, 1, 2)))(q, k, v, cache)
    want = jax.grad(lambda *a: (dense_attention(*a, None) * weight).sum(),
                    (0, 1, 2))(q, k, v, cache)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-5)
