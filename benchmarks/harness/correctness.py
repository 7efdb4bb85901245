"""The comparisons that decide `correct`, outside the measured window,
at full widths, against benchmarks/reference/dqn.py.

Tolerances and their reason. The system computes the network in
bfloat16 (8 bits of mantissa, relative rounding 2^-8 = 0.4% per
operation) and keeps Q-values, TD errors, the loss and the tree in
float32; the reference is float32 at "highest" matmul precision.
Rounding errors of a forward pass scale with the size of the Q-values,
not of the single value, so allowances are a share of the batch's mean
|Q|. Measured (PR 22; CPU and chip, fresh nets and nets after 2,000
steps): the 95th percentile of a Q-value's error is 2.0-2.8% of mean
|Q|; a TD error is made of three Q-values and comes out at 3-4%. A
double-DQN argmax can also flip between two near-tied actions, which
moves single TD errors by the gap between those actions, so single
samples are held to a quantile, not a maximum. Errors are compared
where they are made, in TD space: the system's |TD| is taken back out
of the priority it wrote, since after a live run |TD| has shrunk to
the size of the rounding error and the power would blow that up.

- Q-values: 95% of the batch within 6% of mean |Q| (2-3x the measured
  bf16 error).
- |TD|: 95% of the batch within 15% of mean |Q| (about 4x the
  measured error: an argmax flip or two must not decide a run).
  A path with two bits of mantissa less makes four times the error
  (8-11% and 12-16%) and fails both; benchmarks/tests/test_reference.py holds
  that.
- the batch loss: the system's reported loss equals the weighted
  Huber mean of its own |TD|s to 0.1% (float32 sums in two orders).
  Held to the reference's loss directly it would have to carry the TD
  rounding error times 2/|TD|, which after a live run (|TD| about
  0.02) is 5-10% and did fail one run in twelve at 3%; split this way
  the arithmetic is held exactly and the precision by the |TD| rule.
- ring contents, counters and ledgers: exact.
"""

from __future__ import annotations

import numpy as np

from benchmarks.reference import dqn as ref

Q_REL = 0.06         # share of the batch's mean |Q|
TD_REL = 0.15
QUANTILE = 0.95
LOSS_RTOL = 1e-3
LOSS_ATOL = 1e-7
TREE_ROOT_RTOL = 1e-4   # float32 sums over 2^20 leaves, two orders


def reference_params(sys_params, conv_strides) -> ref.Params:
    """Map the system's flax pytree (models/qnets.NatureDQN) onto the
    reference's plain arrays. Kernel layouts agree (HWIO convs,
    [in, out] dense), so this is renaming only."""
    p = sys_params["params"]
    torso = p["torso"]
    convs = sorted(k for k in torso if k.startswith("Conv_"))
    head = p["DuelingHead_0"]
    f32 = lambda x: np.asarray(x, np.float32)  # noqa: E731
    return ref.Params(
        conv_kernels=[f32(torso[k]["kernel"]) for k in convs],
        conv_biases=[f32(torso[k]["bias"]) for k in convs],
        conv_strides=list(conv_strides),
        dense_kernel=f32(torso["torso_out"]["kernel"]),
        dense_bias=f32(torso["torso_out"]["bias"]),
        value_kernel=f32(head["value"]["kernel"]),
        value_bias=f32(head["value"]["bias"]),
        advantage_kernel=f32(head["advantage"]["kernel"]),
        advantage_bias=f32(head["advantage"]["bias"]))


def within_quantile(got, want, allow: float, quantile=QUANTILE):
    """-> (ok, the quantile of |got - want|): ok when that share of
    the samples is within `allow`."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return False, float("nan")
    err = np.abs(got - want)
    return bool(np.mean(err <= allow) >= quantile), float(
        np.quantile(err, quantile))


def ring_returns_what_was_written(items: dict, expected: dict
                                  ) -> tuple[bool, dict]:
    """Byte-exact: the sampled frame stacks, actions, rewards and
    discounts are what the seed wrote at those indices.
    -> (ok, rows that differ per field)."""
    wrong = {}
    for k in ("obs", "next_obs", "action", "reward", "discount"):
        got, want = np.asarray(items[k]), np.asarray(expected[k])
        differ = (got != want).reshape(got.shape[0], -1).any(axis=1)
        wrong[k] = int(differ.sum())
    return not any(wrong.values()), {"ring_rows_wrong": wrong}


def drawn_once(leaf_ids: np.ndarray) -> np.ndarray:
    """Mask of the draws whose leaf appears once in the batch."""
    _, inverse, counts = np.unique(leaf_ids, return_inverse=True,
                                   return_counts=True)
    return counts[inverse] == 1


def loss_and_priorities_match(online, target, batch: dict, weights,
                              sys_loss: float, sys_priorities,
                              compare: np.ndarray, alpha: float,
                              eps: float, huber_delta: float
                              ) -> tuple[bool, dict]:
    """The system's `learn_k` on `batch` against the reference on the
    same arrays and weights: the |TD| behind each new priority (where
    `compare` is set) by the quantile rule, and the reported loss
    against the weighted Huber mean of those |TD|s."""
    loss, td_abs = ref.double_dqn_loss(
        online, target, batch["obs"], batch["action"], batch["reward"],
        batch["next_obs"], batch["discount"], weights, huber_delta)
    loss, td_abs = float(loss), np.asarray(td_abs)
    q_scale = float(np.abs(np.asarray(
        ref.q_values(online, batch["obs"]))).mean())
    # back from the priority the system wrote to the |TD| it computed:
    # p = (|td| + eps)^alpha
    sys_td = np.maximum(np.asarray(sys_priorities, np.float64), 0.0) ** (
        1.0 / alpha) - eps
    ok_td, td_err = within_quantile(sys_td[compare], td_abs[compare],
                                    TD_REL * q_scale)
    own_loss = float(np.mean(np.asarray(weights, np.float64)
                             * np.asarray(ref.huber(sys_td, huber_delta))))
    ok_loss = bool(np.isfinite(sys_loss) and abs(sys_loss - own_loss)
                   <= LOSS_ATOL + LOSS_RTOL * abs(own_loss))
    return ok_td and ok_loss, {
        "loss_system": sys_loss, "loss_of_system_td": own_loss,
        "loss_reference": loss,
        "td_err_q95": td_err, "td_allow": TD_REL * q_scale,
        "q_abs_mean": q_scale,
        "td_abs_reference_mean": float(td_abs.mean()),
        "priorities_compared": int(compare.sum())}


def q_values_match(params: ref.Params, obs, sys_q) -> tuple[bool, dict]:
    want = np.asarray(ref.q_values(params, obs))
    scale = float(np.abs(want).mean())
    ok, err = within_quantile(sys_q, want, Q_REL * scale)
    return ok, {"q_err_q95": err, "q_allow": Q_REL * scale,
                "q_abs_mean": scale}


def tree_root_is_leaf_sum(tree: np.ndarray) -> bool:
    """tree: [2 * capacity] (root at 1, leaves in the upper half)."""
    tree = np.asarray(tree, np.float64)
    cap = tree.shape[-1] // 2
    root, leaves = tree[..., 1], tree[..., cap:].sum(axis=-1)
    return bool(np.all(np.abs(root - leaves)
                       <= TREE_ROOT_RTOL * np.maximum(leaves, 1e-30)))
