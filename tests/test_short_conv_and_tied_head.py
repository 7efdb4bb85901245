"""The two bare functions the decoder family's sixth net brought
(models/lfm2_moe_q.py), apart from the net: the gated short-convolution
operator against the tap sum on a left-padded array - outputs, the
two-row tail, the gradients of W_in, w, W_out and of the incoming
tail - at one new position and at five, behind a prefix of 0, 1 and 2
rows; and the column read of a head that IS the embedding
(models/q_head.q_at over [A, hidden]) against the dense read, forward
and both gradients, at a vocabulary on and off a lane tile of 128. The
shared filter itself (models/short_conv.py) is Kimi's too: its pinned
program in tests/test_cycle_scopes.py holds it there."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.configs import get_config
from ape_x_dqn_tpu.models.lfm2_moe_q import Lfm2MoeQNet
from ape_x_dqn_tpu.models.q_head import q_at
from ape_x_dqn_tpu.models.short_conv import behind, short_conv

H, TAPS, B = 32, 3, 2


def _operator():
    cfg = get_config("lfm2_tiny_q")
    net = Lfm2MoeQNet(cfg.network.lfm2_moe, "float32")
    k_in, k_w, k_out = jax.random.split(jax.random.key(7), 3)
    # of order 1 each, so that all three matter to the output
    p = {"in_proj": jax.random.normal(k_in, (H, 3 * H)) / np.sqrt(H),
         "conv_weight": jax.random.normal(k_w, (TAPS, H)),
         "out_proj": jax.random.normal(k_out, (H, H)) / np.sqrt(H)}
    return net, p


def _plain(p, u, rows):
    """The operator over `rows` [B, r, H] of z that came before and the
    new positions u, as the sum over taps on a LEFT-PADDED array (zeros
    before the first row there is) -> (output at the new positions, the
    last two rows of z with zeros where there are fewer)."""
    bcx = u @ p["in_proj"]
    gate_b, gate_c, x = bcx[..., :H], bcx[..., H:2 * H], bcx[..., 2 * H:]
    z = jnp.concatenate([rows, gate_b * x], axis=1)
    length = z.shape[1]
    padded = jnp.pad(z, ((0, 0), (TAPS - 1, 0), (0, 0)))
    c = sum(p["conv_weight"][j] * padded[:, j:j + length]
            for j in range(TAPS))[:, rows.shape[1]:]
    return (gate_c * c) @ p["out_proj"], padded[:, -(TAPS - 1):]


@pytest.mark.parametrize("prefix_rows", [0, 1, 2])
@pytest.mark.parametrize("t", [1, 5])
def test_the_conv_operator_is_the_tap_sum_on_a_padded_array(t, prefix_rows):
    net, p = _operator()
    k_u, k_r, k_g = jax.random.split(jax.random.key(t + 10 * prefix_rows), 3)
    u = jax.random.normal(k_u, (B, t, H))
    rows = jax.random.normal(k_r, (B, prefix_rows, H))
    g = jax.random.normal(k_g, (B, t, H))
    # what a prefix of `prefix_rows` rows leaves: zeros on its left
    tail = jnp.pad(rows, ((0, 0), (TAPS - 1 - prefix_rows, 0), (0, 0)))

    def system(p, u, tail):
        out, new_tail, passed = net._conv(p, u, tail)
        return jnp.vdot(out, g), (out, new_tail, passed)

    def plain(p, u, rows):
        out, new_tail = _plain(p, u, rows)
        return jnp.vdot(out, g), (out, new_tail)

    (_, (out, new_tail, passed)), (d_p, d_u, d_tail) = jax.value_and_grad(
        system, argnums=(0, 1, 2), has_aux=True)(p, u, tail)
    (_, (want, want_tail)), (w_p, w_u, w_rows) = jax.value_and_grad(
        plain, argnums=(0, 1, 2), has_aux=True)(p, u, rows)
    tol = dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out, want, **tol)
    assert new_tail.shape == (B, TAPS - 1, H)
    np.testing.assert_allclose(new_tail, want_tail, **tol)
    assert int(passed) == B * t
    for name in ("in_proj", "conv_weight", "out_proj"):
        assert np.abs(w_p[name]).max() > 1e-3, name
        np.testing.assert_allclose(d_p[name], w_p[name], err_msg=name, **tol)
    np.testing.assert_allclose(d_u, w_u, **tol)
    # the incoming tail's gradient: of the rows there are, and nothing
    # reaches further back than the filter (a tail row two behind a
    # window of one token reads the first tap alone)
    np.testing.assert_allclose(d_tail[:, TAPS - 1 - prefix_rows:], w_rows,
                               **tol)
    if prefix_rows:
        assert np.abs(w_rows).max() > 1e-3
    # no prefix is a tail of zeros
    none, none_tail, _ = net._conv(p, u, None)
    zeros, zeros_tail, _ = net._conv(p, u, jnp.zeros((B, TAPS - 1, H)))
    np.testing.assert_array_equal(none, zeros)
    np.testing.assert_array_equal(none_tail, zeros_tail)


def test_the_shared_filter_has_no_activation_and_the_tail_is_what_was_seen():
    """`short_conv` is the sum alone (Kimi puts its SiLU behind it, LFM2
    nothing): linear in its input; `behind` keeps the dtype and its last
    K - 1 rows are the next call's tail."""
    k_x, k_w = jax.random.split(jax.random.key(3))
    x = jax.random.normal(k_x, (B, 6, H))
    w = jax.random.normal(k_w, (4, H))
    seen = behind(None, x, 4)
    assert seen.shape == (B, 9, H) and not np.asarray(seen[:, :3]).any()
    out = short_conv(seen, w, 6)
    np.testing.assert_allclose(short_conv(-2.0 * seen, w, 6), -2.0 * out,
                               rtol=1e-6)
    np.testing.assert_allclose(out[:, 0], w[3] * x[:, 0], rtol=1e-6)
    half = behind(seen[:, 3:6], x[:, 3:].astype(jnp.bfloat16), 4)
    assert half.dtype == jnp.bfloat16
    np.testing.assert_array_equal(half[:, -3:], x[:, 3:].astype(jnp.bfloat16))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("held", [96, 128], ids=["96_ids", "128_ids"])
def test_the_tied_heads_column_read_is_the_dense_read(held, dtype):
    """`q_at(..., by_row=True)` over E [A, hidden] against x E^T and
    its gradients against the matmul's, at a vocabulary that is whole
    lane tiles and at one that is not; ids repeat and the repeats add
    up; E's gradient comes in E's own shape."""
    hidden, dt = 24, jnp.dtype(dtype)
    kx, kw, ki, kg = jax.random.split(jax.random.key(held), 4)
    x = jax.random.normal(kx, (2, 9, hidden), jnp.float32).astype(dt)
    e = jax.random.normal(kw, (held, hidden), jnp.float32)
    ids = jax.random.randint(ki, (2, 9), 0, 4)
    g = jax.random.normal(kg, (2, 9), jnp.float32)

    def dense(x, e):
        q = jnp.einsum("bth,ah->bta", x, e.astype(x.dtype),
                       preferred_element_type=jnp.float32)
        return jnp.take_along_axis(q, ids[..., None], -1)[..., 0]

    got, pull = jax.vjp(lambda x, e: q_at(x, e, ids, by_row=True), x, e)
    want, pull_dense = jax.vjp(dense, x, e)
    exact = dt == jnp.float32
    tol = dict(rtol=1e-5, atol=1e-6) if exact else dict(rtol=2e-2, atol=2e-2)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, **tol)
    (d_x, d_e), (want_x, want_e) = jax.jit(pull)(g), pull_dense(g)
    assert d_x.dtype == dt and d_e.dtype == jnp.float32
    assert d_e.shape == e.shape
    np.testing.assert_allclose(d_x.astype(jnp.float32),
                               want_x.astype(jnp.float32), **tol)
    np.testing.assert_allclose(d_e, want_e, **tol)
    assert not np.asarray(d_e)[4:].any()
    # the untied read over the transposed matrix is the same number
    np.testing.assert_allclose(q_at(x, e.T, ids), got, **tol)


def test_the_nets_head_at_reads_the_embedding_and_repeats_add_in_float32():
    """`Lfm2MoeQNet.head_at` is the read over `embed_tokens`: 1,024
    tokens all take id 5, each adds exactly 1 to every entry of that
    ROW's gradient (x = 1, g = 1): a float32 sum reads 1,024 where a
    bfloat16 sum would stall at 256; the rows read are the rounded
    ones."""
    cfg = get_config("lfm2_tiny_q")
    net = Lfm2MoeQNet(dataclasses.replace(
        cfg.network.lfm2_moe, vocab_size=128), "bfloat16")
    params = {"embed_tokens": jnp.full((128, H), 1.0 + 2.0 ** -10)}
    x = jnp.ones((4, 256, H), jnp.bfloat16)
    ids = jnp.full((4, 256), 5, jnp.int32)
    q, pull = jax.vjp(lambda p: net.head_at(p, x, ids), params)
    np.testing.assert_array_equal(q, np.full((4, 256), H, np.float32))
    (grads,) = jax.jit(pull)(jnp.ones((4, 256), jnp.float32))
    e = np.asarray(grads["embed_tokens"])
    assert e.dtype == np.float32 and e.shape == (128, H)
    np.testing.assert_array_equal(e[5], np.full(H, 1024.0))
    assert not np.delete(e, 5, axis=0).any()
