"""ops/chunked_delta_rule.py against the rule it chunks, one position at
a time in float32: outputs, the final state and the gradients of q, k,
v, g, beta and of the initial state. ISSUE 47: a chunk's solve is
products of whole block diagonal matrices and its walk two products; the
cases hold calls of one, four, five and sixteen chunks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.ops import chunked_delta_rule as cdr

B, H, DK, DV = 2, 3, 8, 5


def recurrence(q, k, v, g, beta, state):
    """S_t = (I - beta k k^T) Diag(exp g) S_{t-1} + beta k v^T; o = S^T q."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x                     # [B, H, .]
        s = jnp.exp(g_t)[..., None] * s
        read = jnp.einsum("bhk,bhkv->bhv", k_t, s)
        s = s + jnp.einsum("bhk,bhv->bhkv", k_t,
                           b_t[..., None] * (v_t - read))
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s)

    with jax.default_matmul_precision("highest"):
        state, o = jax.lax.scan(step, state, tuple(
            jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def inputs(t, seed=0, decay=None, shared=0.0):
    """`decay`: g held at -decay on every channel and position (else
    log-uniform per channel as the net's initialisation gives);
    `shared`: how much of every key is one common vector."""
    r = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731,E501
    q = unit(r.normal(size=(B, t, H, DK))) * DK ** -0.5
    k = unit(r.normal(size=(B, t, H, DK))
             + shared * r.normal(size=(1, 1, H, DK)))
    v = r.normal(size=(B, t, H, DV))
    if decay is None:
        g = -np.exp(r.uniform(np.log(1e-3), np.log(1.6), (B, t, H, DK)))
    else:
        g = np.full((B, t, H, DK), -decay)
    beta = 1.0 / (1.0 + np.exp(-r.normal(size=(B, t, H))))
    state = r.normal(size=(B, H, DK, DV))
    return tuple(jnp.asarray(x, jnp.float32)
                 for x in (q, k, v, g, beta, state))


def close(got, want, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.abs(want).max()), 1e-6)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got / scale, want / scale, atol=tol)


@pytest.mark.parametrize("with_state", [False, True])
@pytest.mark.parametrize("t,chunk", [(32, 8), (64, 16), (64, 64), (8, 2),
                                     (128, 8), (128, 32)])
def test_outputs_and_state_match_the_recurrence(t, chunk, with_state):
    *x, state = inputs(t, seed=t + chunk)
    state = state if with_state else None
    zeros = jnp.zeros((B, H, DK, DV), jnp.float32)
    o, s = cdr.chunked_delta_rule(*x, state, chunk=chunk)
    o_want, s_want = recurrence(*x, zeros if state is None else state)
    close(o, o_want)
    close(s, s_want)
    assert o.dtype == s.dtype == jnp.float32


@pytest.mark.parametrize("t,chunk,with_state", [
    (32, 8, False), (32, 8, True),
    (128, 8, True), (128, 32, True),    # sixteen chunks, four
    (37, 8, True)])                     # five chunks, the last padded
def test_gradients_match_the_recurrence(t, chunk, with_state):
    args = inputs(t, seed=3)
    r = np.random.default_rng(9)
    co = jnp.asarray(r.normal(size=(B, t, H, DV)), jnp.float32)
    cs = jnp.asarray(r.normal(size=(B, H, DK, DV)), jnp.float32)

    def scalar(fn):
        def f(*a):
            state = a[5] if with_state else 0.0 * a[5]
            o, s = fn(*a[:5], state)
            return (o * co).sum() + (s * cs).sum()
        return f

    got = jax.grad(scalar(lambda *a: cdr.chunked_delta_rule(
        *a, chunk=chunk)), argnums=range(6))(*args)
    want = jax.grad(scalar(recurrence), argnums=range(6))(*args)
    for name, a, b in zip(("q", "k", "v", "g", "beta", "state"), got, want):
        if name == "state" and not with_state:
            continue
        close(a, b, 1e-4)


@pytest.mark.parametrize("chunk", [64, 32])     # two chunks, four
def test_strongest_decay_over_a_whole_chunk_is_finite_and_equal(chunk):
    """exp(A_log) = 16 x softplus = 0.1 a position, held over 64
    positions: -G reaches 102, past float32's exp(88)."""
    args = inputs(128, seed=5, decay=1.6)
    o, s = cdr.chunked_delta_rule(*args, chunk=chunk)
    o_want, s_want = recurrence(*args)
    close(o, o_want)
    close(s, s_want)
    grads = jax.grad(lambda *a: cdr.chunked_delta_rule(
        *a, chunk=chunk)[0].sum(), argnums=range(6))(*args)
    want = jax.grad(lambda *a: recurrence(*a)[0].sum(),
                    argnums=range(6))(*args)
    for a, b in zip(grads, want):
        close(a, b, 1e-4)


@pytest.mark.parametrize("chunk", [64, 32])     # two chunks, four
def test_keys_that_are_nearly_one_vector_solve_stably(chunk):
    """Random weights give keys that share one direction: A is near
    beta x the all-ones triangle, where a power series for (I + A)^-1
    loses everything (the module docstring)."""
    args = inputs(128, seed=6, decay=1e-3, shared=30.0)
    o, s = cdr.chunked_delta_rule(*args, chunk=chunk)
    o_want, s_want = recurrence(*args)
    close(o, o_want, 1e-4)
    close(s, s_want, 1e-4)


@pytest.mark.parametrize("t,chunk", [(1, 16), (7, 16), (37, 16), (37, 8)])
def test_a_length_off_a_chunk_changes_neither_output_nor_state(t, chunk):
    args = inputs(t, seed=t)
    o, s, walked = cdr.chunked_delta_rule(
        *args, chunk=chunk, with_chunks=True)
    o_want, s_want = recurrence(*args)
    assert o.shape == (B, t, H, DV)
    close(o, o_want)
    close(s, s_want)
    assert int(walked) == -(-t // chunk)


@pytest.mark.parametrize("t,chunk,products", [
    (128, 32, 11), (4096, 32, 11),      # series 4, two joins 4, W | U_0, 2
    (37, 8, 7), (8, 8, 7),              # no join
    (128, 64, 13)])                     # three joins
def test_a_chunk_is_a_scan_iteration_of_few_whole_products(
        t, chunk, products):
    """What ISSUE 47 changed is in the program: one `lax.scan` of
    ceil(t / chunk) iterations whose body multiplies whole [C, C]
    matrices in the solve (the parent's 8 x 8 blocks took 22 batched
    products at chunks of 32), takes W and U_0 from ONE product and
    walks the state with TWO; `with_chunks` counts its iterations."""
    jaxpr = jax.make_jaxpr(lambda *a: cdr.chunked_delta_rule(
        *a, chunk=chunk, with_chunks=True))(*inputs(t)).jaxpr
    (scan,) = (e for e in jaxpr.eqns if e.primitive.name == "scan")
    assert scan.params["length"] == -(-t // chunk)
    assert str(scan.params["jaxpr"]).count("dot_general") == products
    assert str(jaxpr).count("dot_general") == products  # none outside it


def test_unit_lower_inverse_is_the_inverse():
    r = np.random.default_rng(0)
    a = np.tril(0.1 * r.normal(size=(2, 3, 64, 64)), -1).astype(np.float32)
    inv = cdr._unit_lower_inverse(jnp.asarray(a))
    eye = np.eye(64, dtype=np.float32)
    np.testing.assert_allclose(np.asarray(inv) @ (eye + a),
                               np.broadcast_to(eye, a.shape), atol=1e-5)
    ones = 0.5 * np.tril(np.ones((64, 64), np.float32), -1)
    inv = np.asarray(cdr._unit_lower_inverse(jnp.asarray(ones)))
    np.testing.assert_allclose(inv, np.linalg.inv(eye + ones), atol=1e-6)
