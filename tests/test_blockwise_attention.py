"""ops/blockwise_attention.py against dense masked attention: both
masks (causal; causal inside a window), forward and every gradient,
with and without a cache of earlier positions, in blocks of 4 so that
the diagonal, the window's edge, the front padding and the cache
boundary each fall inside a tile somewhere."""

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
def test_blockwise_attention_equals_dense_masked(t, cached, window):
    """Forward and every gradient, both masks, with and without a
    cache; blocks of 4 so that diagonal, window edge, padding and the
    cache boundary each fall inside a tile somewhere."""
    rng = np.random.default_rng(t + cached)
    new = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa
    q, k, v = new(2, t, 4, 16), new(2, t, 2, 16), new(2, t, 2, 16)
    cache = (new(2, cached, 2, 16), new(2, cached, 2, 16)) if cached else None
    weight = new(2, t, 4, 16)
    blockwise = lambda *a: blockwise_attention(            # noqa: E731
        *a, window=window, block_q=4, block_k=4)
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
