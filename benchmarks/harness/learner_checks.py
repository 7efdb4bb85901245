"""Checks (1)-(3) of `correct`, shared by every traffic kind that ends
with a learner state: one k=1 draw through the system's own `sample_k`
and `learn_k`, on one chip or per shard across the dp mesh.

(1) the drawn batch is, byte for byte, what was written at its indices
    (the kind says what that is: `expected_fn`);
(2) `learn_k` on that batch gives the reference's loss and new
    priorities (benchmarks/harness/correctness.py holds the tolerances);
(3) every shard's sum-tree root equals the sum of its leaves.
"""

from __future__ import annotations

from typing import Callable

import jax
import numpy as np

from benchmarks.harness import correctness


def check_learner(learner, state, cfg, dp: int, conv_strides,
                  expected_fn: Callable[[np.ndarray, np.ndarray, dict],
                                        dict]):
    """expected_fn(shard [n], local transition index [n], drawn items)
    -> the flat transitions the ring must hold there.
    -> (state after the k=1 learn step, checks, notes)."""
    sample, rng = learner.sample_k(state, 1)
    items = jax.tree.map(lambda x: np.asarray(x)[0], sample[0])
    idx, w = np.asarray(sample[1]), np.asarray(sample[2])[0]
    if dp > 1:
        # dist layout: items [dp, b, ...], idx [dp, b] shard-local, raw
        # weights that the learner max-normalises per batch
        items = {k: v.reshape(-1, *v.shape[2:]) for k, v in items.items()}
        shard = np.repeat(np.arange(dp), idx.shape[1])
        w = (w / max(float(w.max()), 1e-12)).reshape(-1)
    else:
        shard = np.zeros(idx.size, np.int64)
    local = idx.reshape(-1).astype(np.int64)
    ring_ok, ring_notes = correctness.ring_returns_what_was_written(
        items, expected_fn(shard, local, items))
    checks = {"ring_returns_what_was_written": ring_ok}

    online = correctness.reference_params(
        jax.device_get(state.params), conv_strides)
    target = correctness.reference_params(
        jax.device_get(state.target_params), conv_strides)
    state, m = learner.learn_k(state._replace(rng=rng), sample, 1)
    tree = np.asarray(state.replay.tree).reshape(dp, -1)
    cap = tree.shape[1] // 2
    ok, notes = correctness.loss_and_priorities_match(
        online, target, items, w, float(m["loss"]),
        tree[shard, cap + local],
        # a leaf drawn twice keeps one write: compare where this draw
        # is the leaf's only one
        correctness.drawn_once(shard * cap + local),
        cfg.replay.alpha, cfg.replay.eps, cfg.learner.huber_delta)
    checks["loss_and_priorities_match_reference"] = ok
    checks["tree_root_is_leaf_sum"] = correctness.tree_root_is_leaf_sum(
        tree)
    return state, checks, {**ring_notes, **notes}
