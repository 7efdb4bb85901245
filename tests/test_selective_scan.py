"""ops/selective_scan.py at tiny widths on the CPU: `chunked` is `step`
iterated is the one-position definition written out here; ragged
`valid` leaves a row's state exactly where its last valid position left
it; a start state is carried; chunk boundaries inside a call and across
calls change nothing; the carry is float32; the gradient exists (the
tiny preset's learner differentiates through `chunked`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.ops import selective_scan as ss

R, D, N = 3, 8, 4


def _inputs(t, seed=0):
    rng = np.random.default_rng(seed)
    f = np.float32
    return {
        "x": rng.normal(size=(R, t, D)).astype(f),
        "delta": np.exp(rng.uniform(np.log(1e-3), np.log(0.5),
                                    (R, t, D))).astype(f),
        "a": -np.exp(rng.normal(size=(N, D))).astype(f),
        "b": rng.normal(size=(R, t, N)).astype(f),
        "c": rng.normal(size=(R, t, N)).astype(f),
        "d": rng.normal(size=D).astype(f),
        "h": rng.normal(size=(R, N, D)).astype(f)}


def _definition(h, x, delta, a, b, c, d, valid):
    """The recurrence as the module's docstring writes it, in float64
    numpy, one row and one position at a time."""
    h = np.asarray(h, np.float64).copy()
    y = np.zeros(x.shape, np.float64)
    for r in range(x.shape[0]):
        for t in range(x.shape[1]):
            if not valid[r, t]:
                continue
            decay = np.exp(delta[r, t][None, :] * a)          # [N, D]
            h[r] = decay * h[r] + (delta[r, t] * x[r, t])[None, :] \
                * b[r, t][:, None]
            y[r, t] = (h[r] * c[r, t][:, None]).sum(axis=0) + d * x[r, t]
    return y, h


@pytest.mark.parametrize("t", [1, 5, ss.CHUNK, ss.CHUNK + 1,
                               3 * ss.CHUNK + 7])
def test_chunked_is_the_definition(t):
    i = _inputs(t)
    valid = np.ones((R, t), bool)
    y, h = ss.chunked(i["h"], i["x"], i["delta"], i["a"], i["b"], i["c"],
                      i["d"])
    want_y, want_h = _definition(valid=valid, **i)
    assert y.dtype == h.dtype == jnp.float32
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h, want_h, rtol=2e-5, atol=2e-5)


def test_step_iterated_is_chunked():
    t = 2 * ss.CHUNK + 3
    i = _inputs(t, seed=1)
    y, h = ss.chunked(i["h"], i["x"], i["delta"], i["a"], i["b"], i["c"],
                      i["d"])
    state, ys = jnp.asarray(i["h"]), []
    for k in range(t):
        y_k, state = ss.step(state, i["x"][:, k], i["delta"][:, k], i["a"],
                             i["b"][:, k], i["c"][:, k], i["d"])
        ys.append(y_k)
    # the same float32 products in the same order: to the last bits
    np.testing.assert_allclose(jnp.stack(ys, 1), y, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(state, h, rtol=1e-6, atol=1e-6)


def test_ragged_valid_leaves_the_state_where_the_last_valid_position_did():
    t = ss.CHUNK + 5
    i = _inputs(t, seed=2)
    counts = np.array([0, 7, t])                # none, some, all
    valid = np.arange(t)[None, :] < counts[:, None]
    y, h = ss.chunked(i["h"], i["x"], i["delta"], i["a"], i["b"], i["c"],
                      i["d"], valid)
    want_y, want_h = _definition(valid=valid, **i)
    np.testing.assert_allclose(h, want_h, rtol=2e-5, atol=2e-5)
    # a row with no valid position keeps its state to the bit
    np.testing.assert_array_equal(np.asarray(h[0]), i["h"][0])
    np.testing.assert_allclose(np.asarray(y)[valid], want_y[valid],
                               rtol=2e-5, atol=2e-5)
    # and `step`'s `valid` does the same for one position
    _, after = ss.step(i["h"], i["x"][:, 0], i["delta"][:, 0], i["a"],
                       i["b"][:, 0], i["c"][:, 0], i["d"],
                       jnp.asarray([False, True, True]))
    np.testing.assert_array_equal(np.asarray(after[0]), i["h"][0])
    assert not np.array_equal(np.asarray(after[1]), i["h"][1])


@pytest.mark.parametrize("cut", [1, ss.CHUNK - 1, ss.CHUNK, ss.CHUNK + 3])
def test_a_boundary_across_calls_changes_nothing(cut):
    t = 2 * ss.CHUNK + 4
    i = _inputs(t, seed=3)
    y, h = ss.chunked(i["h"], i["x"], i["delta"], i["a"], i["b"], i["c"],
                      i["d"])

    def part(state, lo, hi):
        return ss.chunked(state, i["x"][:, lo:hi], i["delta"][:, lo:hi],
                          i["a"], i["b"][:, lo:hi], i["c"][:, lo:hi], i["d"])

    y1, mid = part(i["h"], 0, cut)
    y2, end = part(mid, cut, t)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y, rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(end, h, rtol=1e-6, atol=1e-6)


def test_the_carry_is_float32_whatever_arrives():
    """x, b and c arrive in the compute dtype; the state they leave is
    what float32 copies of the same values leave."""
    t = ss.CHUNK + 2
    i = _inputs(t, seed=4)
    low = {k: jnp.asarray(i[k], jnp.bfloat16) for k in ("x", "b", "c")}
    y, h = ss.chunked(i["h"], low["x"], i["delta"], i["a"], low["b"],
                      low["c"], i["d"])
    y32, h32 = ss.chunked(
        i["h"], *(low[k].astype(jnp.float32) if k in low else i[k]
                  for k in ("x", "delta", "a", "b", "c", "d")))
    assert h.dtype == y.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(h), np.asarray(h32))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y32))
    # no exponent is positive: a long run of large steps decays to
    # nothing and never overflows
    big = jnp.full((R, 4 * ss.CHUNK, D), 50.0)
    _, faded = ss.chunked(i["h"], jnp.zeros_like(big), big, i["a"],
                          jnp.zeros((R, 4 * ss.CHUNK, N)),
                          jnp.zeros((R, 4 * ss.CHUNK, N)), i["d"])
    assert np.isfinite(np.asarray(faded)).all()
    assert float(jnp.abs(faded).max()) < 1e-6


def test_the_gradient_reaches_the_start_state_and_the_inputs():
    t = ss.CHUNK + 3
    i = _inputs(t, seed=5)

    def loss(h, x, delta):
        y, after = ss.chunked(h, x, delta, i["a"], i["b"], i["c"], i["d"])
        return jnp.sum(y ** 2) + jnp.sum(after ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(
        jnp.asarray(i["h"]), jnp.asarray(i["x"]), jnp.asarray(i["delta"]))
    for g in grads:
        assert np.isfinite(np.asarray(g)).all() and float(
            jnp.abs(g).max()) > 0
