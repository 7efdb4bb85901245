"""`correct` for a sequence learner (R2D2), outside the measured window,
at the widths the cell runs: one k=1 draw through the system's own
`sample_k` and `learn_k`, held to benchmarks/reference/r2d2.py.

(a) the drawn sequences - frames, actions, rewards, terminals, mask,
    stored state - are byte for byte what the seed wrote at their
    indices;
(b) the system's Q-values on the drawn batch, the value behind each
    priority it wrote, and its loss, against the reference on the same
    batch, weights and parameters;
(c) the sum-tree's root equals the sum of its leaves;
(e) the program's own `valid_frac` equals the share of trained steps
    the seeded masks and terminals give for that batch: the reference
    applies its own statement of the rule (a step trains if it is real
    data and either step t + n is real data inside the sequence or a
    terminal in [t, t + n) grounds its target) to the drawn masks,
    which (a) holds to the seed's.
((d), finite losses and the step counter, belongs to the traffic kind.)

Tolerances and their reason. The system computes the network in
bfloat16 (7 explicit bits of mantissa) and keeps Q, TD errors, loss and
tree in float32; the reference is float32 at "highest" precision. What
a fair allowance is cannot be said as a share of mean |Q|, as
correctness.py says it for the CNN: here an error made at step t is
carried by (c, h) through up to 80 cell updates, its size follows the
weights and the gates, and Q = V + A - mean A can be small where they
are not. Measured on the chip (PR 26, published widths; 34 readings:
two seeds x fresh / 2,000 / 5,000 steps x four draws, and ten runs of
the cell, three of them fresh): the 95th percentile of the system's Q
error is 0.5-4.4% of mean |Q|, and that of the reference computed with
two bits of mantissa less is 2.1-17.7%. The ranges overlap, so no share
of mean |Q| separates them (a first version held the priorities to 10%
of mean |Q|: one sound run in eleven read 20.5%, where mean |Q| had
fallen to 0.028).

What does separate them is the size of bfloat16's own error on the same
batch and weights. The reference computes itself a second time with
every weight, input and operation's result rounded to bfloat16's
mantissa (`mantissa_bits=7`, reference/r2d2.py); its error against the
float32 reference is the unit. In that unit, the same 34 readings (one
bit less: the 24 draws only):
                         the system   one bit less   two bits less
  Q-values, 95th pct.    0.66-1.12    1.78-2.51      2.94-4.79
  priorities, 95th pct.  0.41-1.48    1.53-3.73      2.15-47
  loss                   0.01-0.24    0.00-0.53      0.00-0.78
(the first row says the rounding model is fair: the system errs as the
rounded reference does. It was not until the model rounded every
operation, the dueling head's v + a - mean(a) included: rounding layer
results only, one sound run of 70 read 1.95 units.) So:

- Q_RATIO: 95% of the batch's Q-values (the system's own forward,
  `learner.net_apply_seq`, over the 80 stored steps from the stored
  state; the 40 trained steps are compared) within 2.0 units: 1.8 times
  the largest sound reading, and the smallest reading at two bits less
  is 1.5 times the limit. This is the rule that holds the precision.
- PRIORITY_RATIO: 95% of the priorities `learn_k` wrote (where a leaf
  was drawn once) within 2.5 units, in |delta| space: p = (eta
  max|delta| + (1 - eta) mean|delta| + eps)^alpha is taken back out of
  the tree as correctness.py does, since after a run |delta| is the
  size of the rounding error and the power would blow that up. A
  priority is 0.9 of the LARGEST |delta| of 40 steps and a double-Q
  argmax that flips between two near-tied actions moves it by their
  gap, so 64 of them spread more than 15,360 Q-values do: 1.7 times
  the largest sound reading; two bits less failed it in 33 of 34
  readings. It holds the loss's arithmetic end to end - target, n-step
  sum, mask, rescaling, eta mix, write-back at the right leaves - and
  what it can of the precision.
- LOSS_RATIO: the loss cannot be rebuilt from the eta mix, so it is
  held to the reference's loss directly. Its unit: to first order the
  loss moves by mean(w) * E[|delta| * |d delta|], so one unit is mean(w)
  * mean |delta| over the trained steps * the 95th percentile of the
  rounded reference's TD error - every trained step off by that much,
  all in one direction. Real rounding errors have both signs and cancel
  in a weighted mean of 2,560 steps, at every precision, so the loss
  cannot tell one from another (the table); 2.0 units, eight times the
  largest sound reading, holds the arithmetic (a wrong reward, frame or
  weight fails it, the tests show) and the precision is Q_RATIO's to
  hold.

Every run says the reference's own reading at two bits less
(`mantissa_bits=5`) in its notes, and whether this comparison would
have passed it: it has to read False, and did in all 34.
"""

from __future__ import annotations

import jax
import numpy as np

from benchmarks.harness import correctness, r2d2_params
from benchmarks.harness import sequence_content as sc
from benchmarks.reference import r2d2 as ref

# the docstring says where each comes from
STATED_MANTISSA_BITS = 7    # bfloat16: `sizes.compute_dtype`
LOWER_MANTISSA_BITS = 5     # two bits less: has to come out not correct
Q_RATIO = 2.0
PRIORITY_RATIO = 2.5
LOSS_RATIO = 2.0
QUANTILE = correctness.QUANTILE
VALID_FRAC_ATOL = 1e-6


def sequences_are_what_was_written(items: dict, expected: dict
                                   ) -> tuple[bool, dict]:
    """Byte-exact over every leaf of the drawn items.
    -> (ok, sequences that differ per leaf)."""
    wrong = {}
    for k in sc.ITEM_KEYS:
        got, want = np.asarray(items[k]), np.asarray(expected[k])
        differ = (got != want).reshape(got.shape[0], -1).any(axis=1)
        wrong[k] = int(differ.sum()) if got.shape == want.shape else -1
    return not any(wrong.values()), {"sequences_wrong": wrong}


def observations(items: dict) -> np.ndarray:
    """The per-step observations [B, L, ...] of drawn items: stored as
    such, or rebuilt from a frame-mode item's single frames (step t
    sees frames t .. t + stack - 1, newest last)."""
    if "obs" in items:
        return np.asarray(items["obs"])
    frames, length = items["seq_frames"], items["actions"].shape[1]
    stack = frames.shape[1] - length + 1
    return np.stack([frames[:, c:c + length] for c in range(stack)],
                    axis=-1)


def reference_on(online: ref.Params, target: ref.Params, items: dict,
                 weights, cfg, conv_strides,
                 mantissa_bits: int | None = None) -> dict:
    """The reference on drawn items -> {"loss", "priorities" [B], "q"
    [B, L - burn_in, A], "td" and "valid" [B, L - burn_in]}; float32
    proper, or with the network rounded to `mantissa_bits`."""
    loss, aux = jax.jit(
        ref.sequence_loss, static_argnames=(
            "burn_in", "n_step", "gamma", "eta", "huber_delta",
            "conv_strides", "mantissa_bits"))(
        online, target, observations(items), items["actions"],
        items["rewards"], items["terminals"], items["mask"],
        items["init_c"], items["init_h"], weights,
        burn_in=cfg.replay.burn_in, n_step=cfg.learner.n_step,
        gamma=cfg.learner.gamma, eta=cfg.replay.priority_eta,
        huber_delta=cfg.learner.huber_delta,
        conv_strides=tuple(conv_strides), mantissa_bits=mantissa_bits)
    return {"loss": float(loss), "q": np.asarray(aux["q"]),
            "priorities": np.asarray(aux["priorities"]),
            "td": np.asarray(aux["td"]), "valid": np.asarray(aux["valid"])}


def matches_reference(got: dict, want: dict, stated: dict,
                      compare: np.ndarray, weight_mean: float
                      ) -> tuple[bool, dict]:
    """`got` (the system's {"q", "priorities" in |delta| space, "loss"})
    against `want` (the float32 reference), in units of the error that
    `stated` (the reference at the stated precision) makes against
    `want` on the same batch. `compare` masks the priorities held."""
    def q95(a, b):
        return float(np.quantile(np.abs(np.asarray(a, np.float64) - b),
                                 QUANTILE))

    trained = want["valid"] > 0
    q_unit = q95(stated["q"], want["q"])
    pri_unit = q95(stated["priorities"][compare],
                   want["priorities"][compare])
    # every trained step's TD error off by the unit, all in one
    # direction: d loss = mean(w) * E[|delta| * |d delta|]
    loss_unit = (weight_mean * float(np.abs(want["td"][trained]).mean())
                 * q95(stated["td"][trained], want["td"][trained]))
    ok_q, q_err = correctness.within_quantile(
        got["q"], want["q"], Q_RATIO * q_unit, QUANTILE)
    ok_pri, pri_err = correctness.within_quantile(
        got["priorities"][compare], want["priorities"][compare],
        PRIORITY_RATIO * pri_unit, QUANTILE)
    loss_allow = LOSS_RATIO * loss_unit
    loss_err = abs(got["loss"] - want["loss"])
    ok_loss = bool(np.isfinite(got["loss"]) and loss_err <= loss_allow)
    return ok_q and ok_pri and ok_loss, {
        "q_err_q95": q_err, "q_unit": q_unit,
        "priority_err_q95": pri_err, "priority_unit": pri_unit,
        "loss_err": loss_err, "loss_unit": loss_unit,
        "loss_allow": loss_allow,
        "ok": {"q": ok_q, "priorities": ok_pri, "loss": ok_loss}}


def agrees_with_reference(online: ref.Params, target: ref.Params,
                          items: dict, weights, sys_q, sys_loss: float,
                          sys_priorities, compare: np.ndarray, cfg,
                          conv_strides) -> tuple[bool, dict]:
    """The system on `items` - its forward's Q-values [B, L, A], the
    loss `learn_k` reported and the priorities it wrote - against the
    reference on the same arrays. The notes carry the same comparison
    made on the reference at two bits of mantissa less, which has to
    fail it."""
    items = {**items, "obs": observations(items)}   # once for the three
    at = lambda bits: reference_on(                      # noqa: E731
        online, target, items, weights, cfg, conv_strides, bits)
    want, stated, lower = (at(None), at(STATED_MANTISSA_BITS),
                           at(LOWER_MANTISSA_BITS))
    w_mean = float(np.mean(weights))
    # back from the stored (p + eps)^alpha to the priority in |delta|
    # space
    sys_pri = np.maximum(np.asarray(sys_priorities, np.float64), 0.0) ** (
        1.0 / cfg.replay.alpha) - cfg.replay.eps
    got = {"q": np.asarray(sys_q)[:, cfg.replay.burn_in:],
           "priorities": sys_pri, "loss": float(sys_loss)}
    ok, notes = matches_reference(got, want, stated, compare, w_mean)
    lower_ok, lower_notes = matches_reference(lower, want, stated,
                                              compare, w_mean)
    return ok, {
        **notes, "loss_system": got["loss"],
        "loss_reference": want["loss"],
        "q_abs_mean": float(np.abs(want["q"]).mean()),
        "weight_mean": w_mean,
        "priority_reference_mean": float(want["priorities"].mean()),
        "priorities_compared": int(compare.sum()),
        "valid_share_reference": float(want["valid"].mean()),
        "two_bits_less": {
            "passes": lower_ok, **{k: lower_notes[k] for k in (
                "q_err_q95", "priority_err_q95", "loss_err", "ok")}}}


def check_learner(learner, state, cfg, conv_strides, expected_fn):
    """expected_fn(leaf indices [n]) -> the items the seed wrote there.
    -> (state after the k=1 learn step, checks, notes)."""
    sample, rng = learner.sample_k(state, 1)
    items = jax.tree.map(lambda x: np.asarray(x)[0], sample[0])
    idx = np.asarray(sample[1]).reshape(-1).astype(np.int64)
    weights = np.asarray(sample[2])[0]
    expected = expected_fn(idx)
    ok, notes = sequences_are_what_was_written(items, expected)
    checks = {"sequences_are_what_was_written": ok}

    online = r2d2_params.reference_params(jax.device_get(state.params))
    target = r2d2_params.reference_params(
        jax.device_get(state.target_params))
    # the system's own forward on the drawn batch (the stacks rebuilt
    # here, once, not by the program), before learn_k donates the state
    items = {**items, "obs": observations(items)}
    sys_q, _ = jax.jit(learner.net_apply_seq)(
        state.params, items["obs"], (items["init_c"], items["init_h"]))
    sys_q = np.asarray(sys_q)
    state, m = learner.learn_k(state._replace(rng=rng), sample, 1)
    tree = np.asarray(state.replay.tree)
    cap = tree.shape[0] // 2
    ok, more = agrees_with_reference(
        online, target, items, weights, sys_q, float(m["loss"]),
        tree[cap + idx], correctness.drawn_once(idx), cfg, conv_strides)
    checks["q_loss_and_priorities_match_reference"] = ok
    checks["tree_root_is_leaf_sum"] = correctness.tree_root_is_leaf_sum(
        tree[None])
    want, got = more["valid_share_reference"], float(m["valid_frac"])
    checks["valid_frac_is_the_seeded_share"] = (
        abs(got - want) <= VALID_FRAC_ATOL)
    return state, checks, {**notes, **more, "valid_frac_system": got}
