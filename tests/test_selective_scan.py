"""ops/selective_scan.py at tiny widths on the CPU: `chunked` is `step`
iterated is the one-position definition written out here; ragged
`valid` leaves a row's state exactly where its last valid position left
it; a start state is carried; chunk boundaries inside a call and across
calls change nothing; the carry is float32; the gradient exists (the
tiny preset's learner differentiates through `chunked`). Then the third
entry, `step_slots` (the decode step's one Pallas kernel over the slot
pool in place), against what it replaced: a gather of the rows' states,
the `where` of the fresh ones, `step` and a scatter back. On the CPU the
kernel's body runs in Pallas's interpreter (the module's `_interpret`);
the lowering for the TPU is read for the alias that makes it in place
(what the chip's compiler makes of the kernel at the published sizes is
tests/test_sum_tree_dense_top.py's last test, the one file that
describes a chip)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.configs import get_config
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


# -- `step_slots` --------------------------------------------------------------

SLOTS = 7           # the pool's first dimension: six sessions + scratch
SCRATCH = SLOTS - 1
TINY = get_config("jamba2_tiny_q").network.jamba
# name: (d_state, channels)
SIZES = {"published_state": (16, 256),
         "tiny_preset": (TINY.mamba_d_state,
                         TINY.mamba_expand * TINY.hidden_size)}

# name: (slot, fresh, valid) a row
ROWS = {
    "one_row": ([3], [0], [1]),
    "all_rows_real": ([3, 0, 5, 1], [0, 0, 0, 0], [1, 1, 1, 1]),
    "a_fresh_row": ([3, 0, 5], [0, 1, 0], [1, 1, 1]),
    "an_invalid_row": ([3, 0, 5], [0, 0, 0], [1, 0, 1]),
    "an_invalid_fresh_row": ([3, 0, 5], [0, 1, 0], [1, 0, 1]),
    # more rows than travel together: x, delta, b, c and y in two fetches
    "a_bucket_with_padding_rows_on_the_scratch_slot": (
        [2, 4, 0, 5, 1] + [SCRATCH] * 11, [0, 1, 0, 0, 1] + [0, 1] * 5 + [0],
        [1, 1, 1, 0, 1] + [0] * 11),
    "rows_that_fill_no_whole_fetch": (
        [5, 4, 3, 2, 1, 0] + [SCRATCH] * 5, [0] * 11, [1] * 6 + [0] * 5),
}


def plain(pool, slot, fresh, valid, x, delta, a, b, c, d):
    """What models/jamba_q.py's decode step did before the kernel:
    `slots.read`, the `where`, `ss.step`, `slots.write`."""
    before = jnp.where(fresh[:, None, None], 0.0, pool[slot])
    y, after = ss.step(before, x, delta, a, b, c, d, valid)
    return y, pool.at[slot].set(after)


def drawn(n, width, rows, seed=0, x_dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    f = jnp.float32
    return {"pool": jnp.asarray(rng.normal(size=(SLOTS, n, width)), f),
            "x": jnp.asarray(rng.normal(size=(rows, width)), x_dtype),
            "delta": jnp.asarray(np.exp(rng.uniform(
                np.log(1e-3), np.log(0.5), (rows, width))), f),
            "a": -jnp.exp(jnp.asarray(rng.normal(size=(n, width)), f)),
            "b": jnp.asarray(rng.normal(size=(rows, n)), f),
            "c": jnp.asarray(rng.normal(size=(rows, n)), f),
            "d": jnp.asarray(rng.normal(size=width), f)}


def _args(i, slot, fresh, valid):
    return (i["pool"], jnp.asarray(slot), jnp.asarray(fresh, bool),
            jnp.asarray(valid, bool), i["x"], i["delta"], i["a"], i["b"],
            i["c"], i["d"])


@pytest.mark.parametrize("x_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("size", SIZES)
@pytest.mark.parametrize("rows", ROWS)
def test_step_slots_is_gather_where_step_scatter(rows, size, x_dtype):
    n, width = SIZES[size]
    slot, fresh, valid = (np.asarray(x) for x in ROWS[rows])
    fresh, valid = fresh.astype(bool), valid.astype(bool)
    i = drawn(n, width, len(slot), x_dtype=x_dtype)
    args = _args(i, slot, fresh, valid)
    y, after = jax.jit(ss.step_slots)(*args)
    want_y, want = plain(*args)
    assert y.dtype == jnp.float32 and after.dtype == jnp.float32
    assert y.shape == i["x"].shape
    real = slot != SCRATCH
    np.testing.assert_allclose(y[real & valid], want_y[real & valid],
                               rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(after[slot[real]], want[slot[real]],
                               rtol=1e-6, atol=1e-6)
    # a row that does not count keeps its state: zeros where it was
    # fresh, its slot's bits where it was not
    for r in np.flatnonzero(real & ~valid):
        np.testing.assert_array_equal(
            after[slot[r]],
            0 * i["pool"][slot[r]] if fresh[r] else i["pool"][slot[r]])
    untouched = np.setdiff1d(np.arange(SLOTS), slot)
    np.testing.assert_array_equal(after[untouched], i["pool"][untouched])


def test_real_rows_do_not_see_what_the_scratch_rows_do():
    """A slot named twice is a hazard on that slot alone: whatever the
    padding rows carry, and however many there are, the real rows'
    outputs and states are the same bits."""
    n, width = SIZES["tiny_preset"]
    run = jax.jit(ss.step_slots)
    i = drawn(n, width, 8)
    slot = [2, 4] + [SCRATCH] * 6
    flags = np.zeros(8, bool)
    y, after = run(*_args(i, slot, flags, ~flags))
    other = drawn(n, width, 8, seed=1)
    mixed = {k: (jnp.concatenate([i[k][:2], other[k][2:]])
                 if k in ("x", "delta", "b", "c") else i[k]) for k in i}
    fresh, valid = flags.copy(), ~flags
    fresh[3], valid[5] = True, False
    y2, after2 = run(*_args(mixed, slot, fresh, valid))
    few = {k: (i[k][:2] if k in ("x", "delta", "b", "c") else i[k])
           for k in i}
    y3, after3 = run(*_args(few, slot[:2], flags[:2], ~flags[:2]))
    for other_y, other_pool in ((y2, after2), (y3, after3)):
        np.testing.assert_array_equal(y[:2], other_y[:2])
        np.testing.assert_array_equal(after[:SCRATCH], other_pool[:SCRATCH])


@pytest.mark.parametrize("width", [2 * 256, 256 + 128])
def test_channels_in_blocks_and_in_one(width, monkeypatch):
    """Channels that fill whole blocks walk them; channels that do not
    are one block."""
    monkeypatch.setattr(ss, "CHANNELS_A_BLOCK", 256)
    i = drawn(16, width, 3, seed=2)
    args = _args(i, [2, 0, 3], [False, True, False], [True, True, True])
    y, after = jax.jit(ss.step_slots)(*args)
    want_y, want = plain(*args)
    np.testing.assert_allclose(y, want_y, rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(after, want, rtol=1e-6, atol=1e-6)


def test_the_donated_pool_is_the_kernels_output(monkeypatch):
    """Lowered for the TPU (nothing runs): the kernel is one
    `tpu_custom_call` whose second output IS its pool operand, and the
    jitted function's donated pool is that output - the block a row
    reads is the block it writes and no other byte moves."""
    monkeypatch.setattr(ss, "_interpret", lambda: False)
    n, width = SIZES["published_state"]
    i = drawn(n, width, 16)
    flags = np.zeros(16, bool)
    # (a function of its own: `jax.jit(ss.step_slots)` has traced these
    # shapes for the interpreter in the tests above)
    text = jax.jit(lambda *args: ss.step_slots(*args),
                   donate_argnums=0).trace(
        *_args(i, np.arange(16) % SLOTS, flags, ~flags)).lower(
            lowering_platforms=("tpu",)).as_text()
    calls = [line for line in text.splitlines() if "custom_call" in line]
    assert len(calls) == 1 and "@tpu_custom_call" in calls[0]
    assert 'kernel_name = "selective_scan_step_slots"' in calls[0]
    # operands: slot, fresh, valid (prefetched), a, d, x, delta, b, c,
    # the pool
    assert ("output_operand_alias<output_tuple_indices = [1], "
            "operand_index = 9, operand_tuple_indices = []>") in calls[0]
    assert "%arg0: tensor<7x16x256xf32> {tf.aliasing_output = 1 : i32}" \
        in text
    assert "stablehlo.gather" not in text and "stablehlo.scatter" not in text
