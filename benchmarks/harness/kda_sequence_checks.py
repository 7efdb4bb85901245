"""`correct` for a net of the decoder family WITH A SCAN LAYER
(models/kimi_linear_q.py), outside the measured window, at the widths
and the batch the cell runs: one k=1 draw through the system's own
`sample_k` and `learn_k`, held to a plain reference whose KDA is the
recurrence one position at a time. decoder_sequence_checks.py's
`check_learner` WITH TWO DIFFERENCES, beside that file and not inside
it because only a `benchmark` PR may edit it
(benchmarks/README_kda_cell.md):

- NO `window_ignored`: that check makes, for every net, the departure
  "the window is the sequence's length" by replacing `Sizes.window`;
  this net has no window to ignore (its layers are KDA, whose memory is
  a state matrix, and full latent attention), so the reading would PASS
  and say nothing. The readings that have to fail here are one bit less
  and the caller's table (`departures`: one decay a head, the short
  convolution left out, RoPE in the MLA layer, SiLU for the gate's
  sigmoid), each held by the same two of the cell's rules as there (Q's
  95th percentile and the gradient's worst and median leaf).
- ONE MORE CHECK, `kda_chunks_counter_matches_the_shapes`: the step's
  own `kda_chunks` reads KDA layers x (chunks of the prefix + chunks of
  the trained steps) and `kda_state_rms_last` is a finite number above
  zero (both go into the notes).

`check_learner`'s frame is that file's, written again (it binds the
departure it makes by name); everything model-free under it is IMPORTED
from the modules that have it: the walks (`reference_on`, `reference_net`,
`gradient_norms`: a KDA layer is one more `kind` that the walk passes
to `ref.block` as it is) and the loss's settings from
decoder_sequence_checks.py, the rules and limits from
token_sequence_checks.py, Q's and the loss's rule and the float32
quantile from afmoe_sequence_checks.py. EVERY LIMIT IS THE FAMILY'S:
this check brings none of its own (PERF.md section 6, PR 46 has the
cell's readings against them).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from ape_x_dqn_tpu.models import decoder_block
from benchmarks.harness import correctness
from benchmarks.harness import token_sequence_checks as limits
from benchmarks.harness.afmoe_sequence_checks import (
    _q95, held_to_reference)
from benchmarks.harness.decoder_sequence_checks import (
    gradient_norms, reference_net, reference_on)
from benchmarks.harness.device import say
from benchmarks.harness.token_sequence_checks import (
    BITS, FLOAT32_MANTISSA_BITS, Q_RATIO, ROWS_RTOL, VALID_FRAC_ATOL,
    gradients_match, routing_agrees, sequences_are_what_was_written)


def check_learner(pair, learner, net, state, cfg, expected_fn,
                  departures: dict | None = None, show_limits: bool = False,
                  note=say):
    """`pair`: (reference module, mapper module). expected_fn(leaf
    indices [n]) -> the items the seed wrote there. -> (state after the
    k=1 learn step WITHOUT its parameters and optimizer state, checks,
    notes). `show_limits`: also the readings that have to fail, one bit
    less and each of `departures` (the module docstring); `note(text)`:
    told as each part ends.

    `learn_k` runs first, on the whole state, and the comparison
    afterwards, on the parameters it started from (kept on the host
    meanwhile): once the step is taken Adam's moments and the updated
    parameters are deleted, and that room is what the gradient program
    and the reference's pieces run in."""
    mapper = pair[1]
    bits = BITS if show_limits else BITS[:2]
    sample, rng = learner.sample_k(state, 1)
    items = jax.tree.map(lambda x: np.asarray(x)[0], sample[0])
    idx = np.asarray(sample[1]).reshape(-1).astype(np.int64)
    weights = np.asarray(sample[2])[0]
    ok, notes = sequences_are_what_was_written(items, expected_fn(idx))
    checks = {"sequences_are_what_was_written": ok}

    before = jax.device_get(state.params)
    state, m = learner.learn_k(state._replace(rng=rng), sample, 1)
    m = jax.device_get(m)
    note("learn_k done")
    tree = np.asarray(state.replay.tree)
    for x in jax.tree.leaves((state.params, state.opt_state)):
        x.delete()
    state = state._replace(params=None, opt_state=None)
    # a target sync at this very step would have changed them
    assert int(state.step) % cfg.learner.target_sync_every
    online, target = jax.device_put(before), state.target_params
    del before

    burn = cfg.replay.burn_in
    sizes = mapper.sizes(decoder_block(cfg.network)[1], net.router_trains)
    # what `learn_k` differentiates, compiled apart because the step
    # keeps its gradient to itself; its aux hands back the Q-values and
    # the selections of this very program, and `grad_norm` ties it to
    # the step that was taken
    (sys_loss, aux), sys_grads = jax.jit(jax.value_and_grad(
        learner.family.loss_fn, has_aux=True))(
        online, target, learner.family.make_batch(items), weights)
    sys_q, topk_on, topk_tg = (np.asarray(aux[k]) for k in (
        "q", "topk_online", "topk_target"))
    del aux
    note("the gradient program done")
    greedy = sys_q.argmax(axis=-1)
    at = reference_on(pair, online, target, items, weights, cfg, sizes,
                      topk_on, topk_tg, greedy, bits)
    want, stated = at[bits[0]], at[bits[1]]
    note("the reference's forward passes done")
    norm_program = float(np.sqrt(sum(
        float(jnp.vdot(g, g)) for g in jax.tree.leaves(sys_grads))))
    cap = tree.shape[0] // 2
    compare = correctness.drawn_once(idx)
    w_mean = float(np.mean(weights))
    # back from the stored (p + eps)^alpha to the priority in |delta|
    # space
    sys_pri = np.maximum(np.asarray(tree[cap + idx], np.float64), 0.0) ** (
        1.0 / cfg.replay.alpha) - cfg.replay.eps
    got = {"q": sys_q, "priorities": sys_pri, "loss": float(m["loss"])}
    # the forward comparison is the host's work and the backward passes
    # the device's: side by side
    # the departures hold the same gradient again: a copy on the host
    kept_grads = jax.device_get(sys_grads) if show_limits else None
    with ThreadPoolExecutor(max_workers=1) as beside:
        forward = beside.submit(held_to_reference, got, want, stated,
                                compare, w_mean)
        rows = gradient_norms(pair, sys_grads, online, at, items, weights,
                              cfg, sizes, topk_on, greedy, bits)
        del sys_grads
        note("the reference's backward passes done")
        ok, more = forward.result()
    for entry in at.values():
        del entry["inputs"]
    checks["q_loss_and_priorities_match_reference"] = ok
    ok_grad, lower_ok_grad, grad_notes = gradients_match(
        rows, float(m["grad_norm"]), norm_program)
    # every leaf, not the worst alone: [its error in units of the stated
    # precision's own, the reference's norm]
    note("gradient leaves " + repr({
        path: [round(float(r[0] / max(r[1], 1e-30)), 2), float(r[3])]
        for path, r in rows.items()}))
    checks["gradients_match_reference"] = ok_grad
    ok_route_on, route_on = routing_agrees(topk_on, want["topk_online"],
                                           want["gap_online"])
    ok_route_tg, route_tg = routing_agrees(topk_tg, want["topk_target"],
                                           want["gap_target"])
    checks["routing_matches_reference_outside_margin"] = (
        ok_route_on and ok_route_tg)
    first = sizes.first_expert
    here = lambda t: int(((t >= first)                   # noqa: E731
                          & (t < first + sizes.experts_held)).sum())
    rows_want = here(topk_on) + here(topk_tg)
    rows_grad_want = here(topk_on[:, :, burn:])
    near = lambda got, exp: abs(got - exp) <= ROWS_RTOL * exp  # noqa: E731
    checks["moe_rows_counter_matches_selection"] = bool(
        near(float(m["moe_rows"]), rows_want)
        and near(float(m["moe_rows_grad"]), rows_grad_want))
    # the scan's own counter: every KDA layer walked every chunk of the
    # prefix and of the trained steps, and left a state that is a number
    chunks_want = net.num_kda_layers * sum(
        -(-n // net.kda_chunk)
        for n in (burn, cfg.replay.seq_length - burn) if n)
    checks["kda_chunks_counter_matches_the_shapes"] = bool(
        float(m["kda_chunks"]) == chunks_want
        and np.isfinite(float(m["kda_state_rms_last"]))
        and float(m["kda_state_rms_last"]) > 0.0)
    checks["tree_root_is_leaf_sum"] = correctness.tree_root_is_leaf_sum(
        tree[None])
    valid_want, valid_got = float(want["valid"].mean()), float(
        m["valid_frac"])
    checks["valid_frac_is_the_seeded_share"] = (
        abs(valid_got - valid_want) <= VALID_FRAC_ATOL)
    # with two precisions the rows' third norm repeats the second
    lower_grad = grad_notes.pop("grad_one_bit_less")
    notes = {
        **notes, **more, **grad_notes, "loss_system": got["loss"],
        "loss_reference": want["loss"],
        "loss_of_the_gradient_program": float(sys_loss),
        "q_abs_mean": float(np.abs(want["q"]).mean()),
        "weight_mean": w_mean,
        "priorities_compared": int(compare.sum()),
        "valid_share_reference": valid_want,
        "valid_frac_system": valid_got,
        "routing_online": route_on, "routing_target": route_tg,
        "moe_rows": [int(m["moe_rows"]), rows_want],
        "moe_rows_grad": [int(m["moe_rows_grad"]), rows_grad_want],
        "moe_load_max_over_mean": float(m["moe_load_max_over_mean"]),
        "kda_chunks": [float(m["kda_chunks"]), chunks_want],
        "kda_state_rms_last": float(m["kda_state_rms_last"])}
    note("the comparison done")
    if show_limits:
        lower_ok, lower_notes = held_to_reference(
            at[bits[2]], want, stated, compare, w_mean, more["q_unit"])
        notes["grad_one_bit_less"] = lower_grad
        notes["one_bit_less"] = {
            "passes": lower_ok and lower_ok_grad,
            **{k: lower_notes[k] for k in (
                "q_err_q95", "priority_err_max_in_q_units", "loss_err",
                "ok")}}

        def held_to(changed: dict) -> dict:
            """The system held to the reference at a departure: Q's
            95th percentile in Q's unit, and every gradient leaf in
            the unit the comparison proper measured for it."""
            sz = sizes._replace(**changed)
            q, _, _, inputs = reference_net(
                pair, online, items["obs"], sz, burn, topk_on,
                FLOAT32_MANTISSA_BITS, keep_inputs=True)
            q_units = _q95(sys_q, q)[0] / max(more["q_unit"], 1e-30)
            apart = gradient_norms(
                pair, {**kept_grads, "layers": list(kept_grads["layers"])},
                online, {FLOAT32_MANTISSA_BITS: {
                    "inputs": inputs, "q_target": want["q_target"]}},
                items, weights, cfg, sz, topk_on, greedy,
                bits=(FLOAT32_MANTISSA_BITS,))
            units = {path: float(apart[path][0] / max(r[1], 1e-30))
                     for path, r in rows.items() if r[3] != 0.0}
            worst = max(units, key=units.get)
            median = float(np.median(list(units.values())))
            return {"passes": bool(q_units <= Q_RATIO
                                   and units[worst] <= limits.GRAD_RATIO
                                   and median <= limits.GRAD_MEDIAN_RATIO),
                    "q_err_q95_in_units": q_units,
                    "grad_worst_leaf": [worst, units[worst]],
                    "grad_median_leaf": median}

        for name, changed in (departures or {}).items():
            notes[name] = held_to(changed)
            note(f"departure {name} done")
    return state, checks, notes
