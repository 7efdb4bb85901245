"""ops/chunked_delta_rule.py against the rule it chunks, one position at
a time in float32: outputs, the final state and the gradients of q, k,
v, g, beta and of the initial state. ISSUE 47: a chunk's solve is
products of whole block diagonal matrices and its walk two products; the
cases hold calls of one, four, five and sixteen chunks. ISSUE 54: the
chunk's backward pass is a rule of its own (`jax.custom_vjp`), held here
to autodiff of the recurrence, which knows nothing of it."""

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


def _gradient_cases():
    """The parent's five cases, then ISSUE 54's: every (t, chunk) of its
    list with and without a state, the cotangent on the outputs alone,
    on the final state alone and on both, the int32 count beside the
    rule in every other case (it changes no gradient: no `float0`)."""
    cases = [
        (32, 8, False, False, "both"), (32, 8, True, False, "both"),
        (128, 8, True, False, "both"),          # sixteen chunks
        (128, 32, True, False, "both"),         # four
        (37, 8, True, False, "both"),           # five chunks, the last padded
        (5, 8, True, True, "state"),            # one chunk, mostly padding
        (128, 64, True, False, "both")]         # a solve of three joins
    for t, chunk in [(32, 8), (64, 16), (64, 64), (8, 2), (37, 16), (37, 8)]:
        for on in ("outputs", "state", "both"):
            for with_state in (False, True):
                case = (t, chunk, with_state, len(cases) % 2 == 0, on)
                if case not in cases:
                    cases.append(case)
    return cases


@pytest.mark.parametrize("t,chunk,with_state,with_chunks,on",
                         _gradient_cases())
def test_gradients_match_the_recurrence(t, chunk, with_state, with_chunks,
                                        on):
    args = inputs(t, seed=3)
    r = np.random.default_rng(9)
    co = jnp.asarray(r.normal(size=(B, t, H, DV)), jnp.float32)
    cs = jnp.asarray(r.normal(size=(B, H, DK, DV)), jnp.float32)
    if on == "state":               # no cotangent on any output position
        co = 0.0 * co
    if on == "outputs":             # none on the final state
        cs = 0.0 * cs

    def scalar(fn):
        def f(*a):
            state = a[5] if with_state else 0.0 * a[5]
            o, s = fn(*a[:5], state)[:2]
            return (o * co).sum() + (s * cs).sum()
        return f

    got = jax.grad(scalar(lambda *a: cdr.chunked_delta_rule(
        *a, chunk=chunk, with_chunks=with_chunks)), argnums=range(6))(*args)
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


@pytest.mark.parametrize("chunk", [64, 32, 8])
def test_strongest_decay_gives_every_input_a_finite_gradient(chunk):
    """ISSUE 54: the backward rule makes the tile again, masked before
    the exponential as the forward's is, so where -G passes 88 inside a
    chunk no infinity meets a zero; cotangents on outputs AND state, all
    six inputs."""
    args = inputs(128, seed=7, decay=1.6)
    r = np.random.default_rng(11)
    co = jnp.asarray(r.normal(size=(B, 128, H, DV)), jnp.float32)
    cs = jnp.asarray(r.normal(size=(B, H, DK, DV)), jnp.float32)

    def scalar(fn):
        def f(*a):
            o, s = fn(*a)
            return (o * co).sum() + (s * cs).sum()
        return f

    got = jax.grad(scalar(lambda *a: cdr.chunked_delta_rule(
        *a, chunk=chunk)), argnums=range(6))(*args)
    want = jax.grad(scalar(recurrence), argnums=range(6))(*args)
    assert len(got) == 6
    for a, b in zip(got, want):
        close(a, b, 1e-4)               # `close` asserts finite first


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


@pytest.mark.parametrize("t,chunk,forward", [
    (128, 32, 11), (37, 8, 7), (128, 64, 13)])
def test_the_backward_pass_is_a_scan_of_eight_products(t, chunk, forward):
    """ISSUE 54: the chunk's backward rule is written out. The gradient
    program is two scans over the chunks: the forward rule's (the
    forward's products, nothing more) and a backward one whose body holds
    EIGHT products whatever the chunk - W | U_0 and W S_0 made again, the
    carry's four transposed, and the solve's two (d rhs = T^T d wu, dA =
    -(d rhs) wu^T): `_unit_lower_inverse` is not run again, T comes saved.
    Autodiff under a `jax.checkpoint` held 33 at chunks of 32 (the
    forward's 11 again and 22 transposes)."""
    def loss(*a):
        o, s, _ = cdr.chunked_delta_rule(*a, chunk=chunk, with_chunks=True)
        return (o * o).sum() + (s * s).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=range(6)))(
        *inputs(t)).jaxpr
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [-(-t // chunk)] * 2
    assert [e.params["reverse"] for e in scans] == [False, True]
    products = [str(e.params["jaxpr"]).count("dot_general") for e in scans]
    assert products == [forward, 8]
    assert str(jaxpr).count("dot_general") == forward + 8
    # what the forward scan hands the backward one a chunk: S_0 and T | B
    # ([C, 2C], one array), nothing of the tile's size and no W | U_0
    stacked = [v.aval.shape[1:] for v in
               scans[0].outvars[scans[0].params["num_carry"]:]]
    assert sorted(stacked) == sorted([
        (B, H, chunk, DV), (B, H, DK, DV), (B, H, chunk, 2 * chunk)])
    # a `custom_vjp`'s backward function opens no scope of its own: every
    # op of the backward scan's body names `kda.scan` > `kda.scan.back`
    # (what `learner.kda_scan_share` reads), the forward's none of it
    under = cdr.SCOPE + "/" + cdr.BACK
    assert all(str(e.source_info.name_stack).startswith(under)
               for e in scans[1].params["jaxpr"].jaxpr.eqns)
    assert not any(cdr.BACK in str(e.source_info.name_stack)
                   for e in scans[0].params["jaxpr"].jaxpr.eqns)


@pytest.mark.parametrize("t,chunk", [(37, 16), (37, 8), (5, 8)])
def test_a_padded_tail_takes_no_gradient_and_passes_the_states(t, chunk):
    """ISSUE 54: the rule on a chunk whose tail is padding (g = 0, beta
    = 0, zeros, as `chunked_delta_rule` pads, whose slice leaves no
    cotangent on a padded output) gives the padded q, k, v and beta
    cotangents of exactly zero (g's is finite and not zero - the state
    after the chunk does depend on it - and the pad's transpose drops
    it), and a chunk that is all padding hands dS back as it came."""
    *x, state = inputs(chunk, seed=t)
    real = t % chunk
    keep = (jnp.arange(chunk) < real).astype(jnp.float32)
    tail = lambda a: keep.reshape((chunk,) + (1,) * (a.ndim - 3))  # noqa: E731,E501
    q, k, v, g, beta = (jnp.moveaxis(a, 1, 2) for a in x)   # [B, H, C, ..]
    q, k, v, g, beta = (a * tail(a) for a in (q, k, v, g, beta))
    r = np.random.default_rng(t)
    d_s1 = jnp.asarray(r.normal(size=state.shape), jnp.float32)
    d_o = jnp.asarray(r.normal(size=(B, H, chunk, DV)), jnp.float32)
    d_o = d_o * tail(d_o)
    _, back = jax.vjp(cdr._chunk, state, q, k, v, g, beta)
    d_s0, *d_x = (np.asarray(d) for d in back((d_s1, d_o)))
    assert np.isfinite(d_s0).all() and all(np.isfinite(d).all() for d in d_x)
    for name, d in zip("qkvgb", d_x):
        assert d[:, :, :real].any()
        assert name == "g" or not d[:, :, real:].any()
    zeros = tuple(0.0 * a for a in (q, k, v, g, beta))
    (s1, _), back = jax.vjp(cdr._chunk, state, *zeros)
    d_s0, dq, dk, dv, _, d_beta = back((d_s1, 0.0 * d_o))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(state))
    np.testing.assert_array_equal(np.asarray(d_s0), np.asarray(d_s1))
    assert not any(np.asarray(d).any() for d in (dq, dk, dv, d_beta))


@pytest.mark.parametrize("differentiated", [False, True])
def test_two_calls_at_one_shape_trace_the_chunk_once(
        monkeypatch, differentiated):
    """ISSUE 54: the walk is a `jax.jit(..., inline=True)`, so a net's
    KDA layers (one shape) trace the chunk's forward ONCE, as the
    `jax.checkpoint` this replaced did, and not once a call as a bare
    `jax.custom_vjp` would (set-up time: the module's `_walk`). Under
    `jax.grad` that is twice whatever the calls: the walk, and the
    forward rule when the walk is differentiated."""
    traced = []
    tile = cdr._tile
    monkeypatch.setattr(
        cdr, "_tile", lambda *a: traced.append(1) or tile(*a))
    # shapes no other test of this file walks: `_walk`'s cache is the
    # process's
    args = inputs(24 if differentiated else 20, seed=1)

    def three(*a):
        o1, s = cdr.chunked_delta_rule(*a, chunk=4)
        o2, s = cdr.chunked_delta_rule(*a[:5], s, chunk=4)
        o3, s = cdr.chunked_delta_rule(*a[:5], s, chunk=4)
        return (o1 * o2 * o3).sum() + (s * s).sum()

    if differentiated:
        three = jax.grad(three, argnums=range(6))
    jaxpr = jax.make_jaxpr(three)(*args)
    assert len(traced) == (2 if differentiated else 1)
    scans = str(jaxpr).count(" scan[")
    assert scans == (6 if differentiated else 3)    # inlined: all there


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
