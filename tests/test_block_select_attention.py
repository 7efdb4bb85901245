"""ops/block_select_attention.py's `attend_range` (a decode step's walk
of each row's own range, ISSUE 60) in Pallas's interpreter on the CPU,
held to `attend_gathered` over `dense_blocks`: the same answers to the
order of a float32 sum (an online softmax rounds against a running
maximum), the keys the kernel's own mask admitted, the positions its
tiles fetched."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.ops import block_select_attention as bsa

BLOCK, TILE, TILES = 8, 16, 4
SPAN = TILE * TILES                     # positions a range may hold
SHARED = 12 * BLOCK                     # what sessions share of the pool
SZ = bsa.Sizes(BLOCK, BLOCK, BLOCK, 0, 0, 0, 0)
GROUP, D = 4, 16


def _pools(g_heads: int, dtype):
    rng = np.random.default_rng(g_heads)
    shape = (g_heads, SHARED + SPAN, D)          # the tail: one whole range
    return (jnp.asarray(rng.normal(size=shape), dtype),
            jnp.asarray(rng.normal(size=shape), dtype))


def _gathered(q, kpool, vpool, base, t):
    blocks = SPAN // BLOCK
    every = jnp.broadcast_to(bsa.dense_blocks(t, blocks, SZ)[:, None],
                             (q.shape[0], q.shape[1], blocks))
    return bsa.attend_gathered(q, kpool, vpool, base, every, t, SZ)


@pytest.mark.parametrize("context", [1, TILE - 1, TILE, TILE + 1, SPAN])
@pytest.mark.parametrize("g_heads", [1, 2])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_range_walk_is_the_gathered_attention(dtype, g_heads, context):
    """Five rows a batch: the context under test at a base that is a
    multiple of the block and not of the tile, at one that is both, and
    on the pool's LAST range (the scratch range, whose last tile ends
    with the array); a row mid-way through its second tile; a padding
    row on the scratch base at t = 0."""
    dt = jnp.dtype(dtype)
    kpool, vpool = _pools(g_heads, dt)
    base = jnp.asarray([BLOCK, 2 * TILE, SHARED, 3 * BLOCK, SHARED],
                       jnp.int32)
    t = jnp.asarray([context - 1] * 3 + [TILE + 5, 0], jnp.int32)
    q = jnp.asarray(np.random.default_rng(context).normal(
        size=(5, g_heads, GROUP, D)), dt)
    o, keys, fetched = bsa.attend_range(q, kpool, vpool, base, t, SZ, TILE,
                                        TILES)
    assert o.shape == q.shape and o.dtype == jnp.float32
    assert keys.dtype == fetched.dtype == jnp.int32
    want = _gathered(q, kpool, vpool, base, t)
    assert float(jnp.abs(want).max()) > 0.1
    # bfloat16: the probabilities are rounded to 8 bits against another
    # maximum before the value product
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(o, want, atol=tol, rtol=0)
    assert np.asarray(keys).tolist() == (np.asarray(t) + 1).tolist()
    walked = -(-(np.asarray(t) + 1) // TILE)
    assert np.asarray(fetched).tolist() == (walked * TILE).tolist()


def test_the_count_is_the_kernels_own_mask_and_the_walk_is_bounded():
    """A position the kernel's mask does not admit is not counted (t one
    short reads one key less, t = -1 none and answers zeros), and a t
    past the range walks `TILES` tiles and no further: the last range's
    last tile ends with the pool."""
    kpool, vpool = _pools(1, jnp.float32)
    q = jnp.ones((4, 1, GROUP, D), jnp.float32)
    base = jnp.asarray([BLOCK, BLOCK, BLOCK, SHARED], jnp.int32)
    t = jnp.asarray([20, 19, -1, SPAN + 40], jnp.int32)
    o, keys, fetched = bsa.attend_range(q, kpool, vpool, base, t, SZ, TILE,
                                        TILES)
    assert np.asarray(keys).tolist() == [21, 20, 0, SPAN]
    assert np.asarray(fetched).tolist() == [2 * TILE, 2 * TILE, TILE, SPAN]
    assert not np.asarray(o[2]).any() and np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(
        o[3], _gathered(q, kpool, vpool, base, jnp.full(4, SPAN - 1))[3],
        atol=1e-5, rtol=0)


def test_one_trace_serves_every_call_site():
    """The kernel sits under a `jax.jit` of its own: two layers' calls in
    one program trace its body once (a call site lowered by itself cost
    0.1 s before the compile cache is asked: PERF.md section 6, PR 58)."""
    kpool, vpool = _pools(1, jnp.float32)
    q = jnp.ones((3, 1, GROUP, D), jnp.float32)
    base = jnp.asarray([0, BLOCK, SHARED], jnp.int32)
    t = jnp.asarray([3, 30, 0], jnp.int32)

    def two_layers(q, kpool, vpool, base, t):
        first, _, _ = bsa.attend_range(q, kpool, vpool, base, t, SZ, TILE,
                                       TILES)
        second, _, _ = bsa.attend_range(q + 1, vpool, kpool, base, t, SZ,
                                        TILE, TILES)
        return first + second

    text = jax.jit(two_layers).lower(q, kpool, vpool, base, t).as_text()
    assert text.count("func.func private @_attend_range") == 1
    assert text.count("call @_attend_range") == 2


def test_a_narrow_head_is_refused_where_the_chips_compiler_would(monkeypatch):
    monkeypatch.setattr(bsa, "_interpret", lambda: False)
    kpool, vpool = _pools(1, jnp.bfloat16)
    with pytest.raises(NotImplementedError, match="128 lanes"):
        bsa.attend_range(jnp.ones((1, 1, GROUP, D), jnp.bfloat16), kpool,
                         vpool, jnp.zeros(1, jnp.int32),
                         jnp.zeros(1, jnp.int32), SZ, TILE, TILES)
