"""ops/lightning_attention.py's third form, `step_slots` (the decode
step's one Pallas kernel over the slot pool in place), against what it
replaced: a gather of the rows' matrices, `step`, the `where` of the
rows that count and a scatter back. On the CPU the kernel's body runs in
Pallas's interpreter (the module's `_interpret`); the lowering for the
TPU is read for the alias that makes it in place (what the chip's
compiler makes of the whole decode program is
tests/test_sum_tree_dense_top.py's last test, the one file that
describes a chip)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.configs import get_config
from ape_x_dqn_tpu.ops import lightning_attention as la

SLOTS = 7           # the pool's first dimension: six sessions + scratch
SCRATCH = SLOTS - 1
TINY = get_config("minicpm_sala_tiny_q").network.minicpm_sala
SIZES = {"published_head": (4, 128),
         "tiny_preset": (TINY.lightning_nh, TINY.lightning_head_dim)}

# name: (slot, fresh, valid) a row
ROWS = {
    "all_rows_real": ([3, 0, 5, 1], [0, 0, 0, 0], [1, 1, 1, 1]),
    "a_fresh_row": ([3, 0, 5], [0, 1, 0], [1, 1, 1]),
    "an_invalid_row": ([3, 0, 5], [0, 0, 0], [1, 0, 1]),
    "an_invalid_fresh_row": ([3, 0, 5], [0, 1, 0], [1, 0, 1]),
    "padding_rows_on_the_scratch_slot": (
        [2, 4, SCRATCH, SCRATCH, SCRATCH], [0, 1, 0, 1, 0], [1, 1, 1, 1, 1]),
}


def plain(pool, slot, fresh, valid, q, k, v, slope, scale):
    """What models/minicpm_sala_q.py's decode step did before the
    kernel: `slots.read`, `la.step`, the `where`, `slots.write`."""
    matrix = jnp.where(fresh[:, None, None, None], 0.0, pool[slot])
    o, after = la.step(q, k, v, matrix, slope, scale)
    after = jnp.where(valid[:, None, None, None], after, matrix)
    return o, pool.at[slot].set(after)


def drawn(heads, d, rows, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    pool = jax.random.normal(keys[0], (SLOTS, heads, d, d))
    q, k, v = (jax.random.normal(x, (rows, heads, d)) for x in keys[1:])
    return pool, q, k, v


@pytest.mark.parametrize("size", SIZES)
@pytest.mark.parametrize("rows", ROWS)
def test_step_slots_is_gather_step_where_scatter(rows, size):
    heads, d = SIZES[size]
    slot, fresh, valid = (np.asarray(x) for x in ROWS[rows])
    fresh, valid = fresh.astype(bool), valid.astype(bool)
    pool, q, k, v = drawn(heads, d, len(slot))
    slope, scale = la.slopes(heads), d ** -0.5
    args = (jnp.asarray(slot), jnp.asarray(fresh), jnp.asarray(valid),
            q, k, v, slope, scale)
    o, after = jax.jit(la.step_slots, static_argnums=8)(pool, *args)
    want_o, want = plain(pool, *args)
    assert o.dtype == jnp.float32 and after.dtype == jnp.float32
    real = slot != SCRATCH
    np.testing.assert_allclose(o[real & valid], want_o[real & valid],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(after[slot[real]], want[slot[real]],
                               rtol=1e-6, atol=1e-6)
    # a row that does not count keeps its matrix: zeros where it was
    # fresh, its slot's bits where it was not
    for i in np.flatnonzero(real & ~valid):
        np.testing.assert_array_equal(
            after[slot[i]], 0 * pool[slot[i]] if fresh[i] else pool[slot[i]])
    untouched = np.setdiff1d(np.arange(SLOTS), slot)
    np.testing.assert_array_equal(after[untouched], pool[untouched])


def test_real_rows_do_not_see_what_the_scratch_rows_do():
    """A slot named twice is a hazard on that slot alone: whatever the
    padding rows carry, and however many there are, the real rows'
    outputs and matrices are the same bits."""
    heads, d = SIZES["tiny_preset"]
    slope, scale = la.slopes(heads), 0.25
    run = jax.jit(la.step_slots, static_argnums=8)
    pool, q, k, v = drawn(heads, d, 8)
    slot = jnp.asarray([2, 4] + [SCRATCH] * 6)
    flags = jnp.zeros(8, bool)
    o, after = run(pool, slot, flags, ~flags, q, k, v, slope, scale)
    _, q2, k2, v2 = drawn(heads, d, 8, seed=1)
    mixed = [jnp.concatenate([a[:2], b[2:]])
             for a, b in ((q, q2), (k, k2), (v, v2))]
    o2, after2 = run(pool, slot, flags.at[3].set(True),
                     (~flags).at[5].set(False), *mixed, slope, scale)
    o3, after3 = run(pool, slot[:2], flags[:2], ~flags[:2], q[:2], k[:2],
                     v[:2], slope, scale)
    for other_o, other in ((o2, after2), (o3, after3)):
        np.testing.assert_array_equal(o[:2], other_o[:2])
        np.testing.assert_array_equal(after[:SCRATCH], other[:SCRATCH])


@pytest.mark.parametrize("heads", [2 * la.HEADS_A_BLOCK,
                                   la.HEADS_A_BLOCK + 1])
def test_heads_in_blocks_and_in_one(heads):
    """Heads that fill whole blocks walk them; heads that do not are one
    block."""
    pool, q, k, v = drawn(heads, 16, 3, seed=2)
    args = (jnp.asarray([2, 0, 3]), jnp.asarray([False, True, False]),
            jnp.ones(3, bool), q, k, v, la.slopes(heads), 0.25)
    o, after = jax.jit(la.step_slots, static_argnums=8)(pool, *args)
    want_o, want = plain(pool, *args)
    np.testing.assert_allclose(o, want_o, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(after, want, rtol=1e-6, atol=1e-6)


def test_the_donated_pool_is_the_kernels_output(monkeypatch):
    """Lowered for the TPU (nothing runs): the kernel is one
    `tpu_custom_call` whose second output IS its pool operand, and the
    jitted function's donated pool is that output - the block a row
    reads is the block it writes and no other byte moves."""
    monkeypatch.setattr(la, "_interpret", lambda: False)
    heads, d = SIZES["published_head"]
    pool, q, k, v = drawn(heads, d, 4)
    flags = jnp.zeros(4, bool)
    text = jax.jit(la.step_slots, static_argnums=8, donate_argnums=0).trace(
        pool, jnp.arange(4), flags, ~flags, q, k, v, la.slopes(heads),
        0.25).lower(lowering_platforms=("tpu",)).as_text()
    calls = [line for line in text.splitlines() if "custom_call" in line]
    assert len(calls) == 1 and "@tpu_custom_call" in calls[0]
    assert 'kernel_name = "lightning_step_slots"' in calls[0]
    # operands: slot, fresh, valid (prefetched), lambda, q, k, v, the pool
    assert ("output_operand_alias<output_tuple_indices = [1], "
            "operand_index = 7, operand_tuple_indices = []>") in calls[0]
    assert "%arg0: tensor<7x4x128x128xf32> {tf.aliasing_output = 1 : i32}" \
        in text
    assert "stablehlo.gather" not in text and "stablehlo.scatter" not in text
