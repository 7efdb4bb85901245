"""`correct` for a net of the decoder family WITH CONV LAYERS AND A TIED
HEAD (models/lfm2_moe_q.py), outside the measured window, at the widths
and the batch the cell runs: one k=1 draw through the system's own
`sample_k` and `learn_k`, held to a plain reference whose conv operator
is the sum over taps on a left-padded array and whose head is x E^T,
whole. decoder_sequence_checks.py's `check_learner` WITH THREE
DIFFERENCES, beside that file and not inside it because only a
`benchmark` PR may edit it (benchmarks/README_conv_cell.md):

- NO `window_ignored`: that check makes, for every net, the departure
  "the window is the sequence's length" by replacing `Sizes.window`;
  this net has no window to ignore. What a conv layer keeps of the
  prefix is two rows, and THIS net's `window_ignored` is
  `conv_tail_ignored`, zeros where they belong: one of the caller's
  table (`departures`), beside the second gate left out, SiLU added
  behind the filter, the q/k norms left out and an untied head. Each is
  held by the cell's own rules (Q's 95th percentile, over the trained
  positions and behind the prefix, and the gradient's worst and median
  leaf) and has to fall to one.
- ONE MORE RULE IN `correct`, `q_behind_the_prefix_matches_reference`
  (`q_behind_the_prefix`): Q's rule of the family - 95% of the values
  within Q_RATIO of the stated precision's own 95th-percentile error -
  read over the first K - 1 TRAINED positions alone, the only ones
  whose filter reaches into the prefix, in the unit the stated
  precision reads on that same slice. It is what holds the two rows a
  conv layer keeps of the burn-in: a program that drops them moves two
  positions of 12,288, which is no part of a 95th percentile over all
  of them (1.0004 units there, 32.9 on the slice: PERF.md section 6,
  PR 50). The check that decides `correct` and the departures' table
  read it through the one function.
- THE TIED MATRIX IS ONE LEAF. The family's backward walk
  (`decoder_sequence_checks.gradient_norms`) records `lm_head` and
  `embed_tokens` apart, the reference's gradient of each use against
  the system's leaf of that name; here the system has ONE leaf whose
  gradient is the sum of both uses, so the walk's two ends are written
  again (`gradient_norms` below): the head's part is kept while the
  layers are walked and the lookup's part added before the one
  comparison. The layers between are walked as there, through the same
  `ref.block`; the forward walks (`reference_on`, `reference_net`) are
  that file's, IMPORTED, over the system's pytree under the names they
  read (`lfm2_params.untied_view`).
- AND ONE MORE CHECK, `conv_positions_counter_matches_the_shapes`: the
  step's own `conv_positions` reads conv layers x sequence length x
  batch.

EVERY LIMIT IS THE FAMILY'S: the rules and limits from
token_sequence_checks.py, Q's and the loss's rule and the float32
quantile from afmoe_sequence_checks.py, the loss's settings from
decoder_sequence_checks.py. This check brings no number of its own -
the slice's rule is Q_RATIO and QUANTILE again - and PERF.md section 6,
PR 50 has the cell's readings against each, the slice's two included.
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
    LOSS_STATICS, _loss_settings, reference_net, reference_on)
from benchmarks.harness.device import say
from benchmarks.harness.token_sequence_checks import (
    BITS, FLOAT32_MANTISSA_BITS, Q_RATIO, QUANTILE, ROWS_RTOL,
    VALID_FRAC_ATOL, _leaf_norms, gradients_match, routing_agrees,
    sequences_are_what_was_written)

TIED, FINAL_NORM = "embed_tokens", "embedding_norm"


def q_behind_the_prefix(got_q, want_q, unit: float,
                        behind: int) -> tuple[bool, dict]:
    """Q's rule of the family on the first `behind` trained positions
    (the module docstring): QUANTILE of |got - want| there within
    Q_RATIO of `unit`, the stated precision's own QUANTILE error on the
    same slice. q: [B, trained positions, A]."""
    err, share = _q95(got_q[:, :behind], want_q[:, :behind], Q_RATIO * unit)
    return share >= QUANTILE, {
        "q_err_q95_behind_the_prefix": err,
        "q_unit_behind_the_prefix": unit,
        "q_err_q95_behind_the_prefix_in_units": err / max(unit, 1e-30),
        "q_share_within_limit_behind_the_prefix": share}


def gradient_norms(pair, sys_grads: dict, online: dict, at: dict,
                   items: dict, weights, cfg, sizes, forced_online, greedy,
                   bits: tuple = BITS) -> dict:
    """decoder_sequence_checks.gradient_norms for a net whose head is
    its embedding: the same walk - head, the layers last to first,
    embedding, ONE SEQUENCE AND ONE PIECE ON THE DEVICE AT A TIME, from
    the layer inputs the forward pass kept - with the tied matrix's two
    parts SUMMED before they are held to the system's one leaf.
    `online`: the system's own pytree. -> {leaf path: `_leaf_norms`}."""
    ref, mapper = pair
    burn, n = cfg.replay.burn_in, items["obs"].shape[0]

    def head_loss(ends, x, q_t, greedy, actions, rewards, terminals, mask,
                  weight, *, sz, mantissa_bits, **settings):
        q = ref.head(ends, x, sz, mantissa_bits)[:, burn:]
        return ref.td_loss(q, q_t, actions, rewards, terminals, mask,
                           weight, greedy=greedy, **settings)[0] / n

    def block_pullback(p, x, ct, forced, tokens, layer, *, sz, kind,
                       mantissa_bits):
        return jax.vjp(lambda p_, x_: ref.block(
            p_, x_, sz, burn, forced, mantissa_bits, tokens, layer,
            kind)[0], p, x)[1](ct)

    def embed_pullback(table, tokens, ct, mantissa_bits):
        return jax.vjp(lambda e: ref.embed(
            {"embed": e}, tokens, mantissa_bits), table)[1](ct)[0]

    head_grad = jax.jit(jax.grad(head_loss, argnums=(0, 1)),
                        static_argnames=("sz",) + LOSS_STATICS)
    block_pull = jax.jit(block_pullback, static_argnames=("sz", "kind"))
    embed_pull = jax.jit(embed_pullback)
    rows = {}

    def add(total, g):
        # fenced: a host that runs ahead of the device would hold
        # several pieces' arguments and results at once
        return jax.block_until_ready(
            g if total is None else jax.tree.map(jnp.add, total, g))

    def record(prefix: str, got: dict, per_bits: dict):
        others = [jax.tree.leaves(per_bits[m]) for m in bits]
        others += others[-1:] * (len(BITS) - len(bits))
        flat = jax.tree_util.tree_flatten_with_path(got)[0]
        for i, (path, leaf) in enumerate(flat):
            rows[prefix + jax.tree_util.keystr(path)] = np.asarray(
                _leaf_norms(leaf, *(o[i] for o in others)))

    trained = [np.asarray(items[k])[:, burn:] for k in (
        "actions", "rewards", "terminals", "mask")]
    tokens = np.asarray(items["obs"])
    weights = np.asarray(weights)
    ends = {"final_norm": online[FINAL_NORM], "head": online[TIED]}
    ct = {m: [None] * n for m in bits}
    acc, tied = {}, {}
    for m in bits:
        total = None
        for b in range(n):
            g, ct[m][b] = head_grad(
                ends, at[m]["inputs"][-1][b], at[m]["q_target"][b:b + 1],
                greedy[b:b + 1], *(x[b:b + 1] for x in trained),
                weights[b:b + 1], sz=sizes, mantissa_bits=m,
                **_loss_settings(cfg))
            total = add(total, g)
            del g
        acc[m] = {FINAL_NORM: total["final_norm"]}
        tied[m] = total["head"]         # the head's part; the lookup's below
    record("", {FINAL_NORM: sys_grads[FINAL_NORM]}, acc)

    layers = mapper.num_layers(online)
    routed_before = np.cumsum([0] + [
        "experts" in online["layers"][i]["mlp"] for i in range(layers)])
    for index in reversed(range(layers)):
        p = mapper.reference_layer(online, index)
        routed = "dense" not in p
        for m in bits:
            total = None
            for b in range(n):
                g, ct[m][b] = block_pull(
                    p, at[m]["inputs"][index][b], ct[m][b],
                    (forced_online[routed_before[index], b:b + 1]
                     if routed else None), tokens[b:b + 1], np.int32(index),
                    sz=sizes, kind=sizes.layer_types[index],
                    mantissa_bits=m)
                total = add(total, g)
                del g
            acc[m] = mapper.system_layer_gradients(total)
        del p
        record(f"['layers'][{index}]", sys_grads["layers"][index], acc)
        sys_grads["layers"][index] = None       # compared: make room

    for m in bits:
        for b in range(n):
            tied[m] = add(tied[m], embed_pull(
                online[TIED], tokens[b:b + 1], ct[m][b], m))
        acc[m] = {TIED: tied[m]}
    record("", {TIED: sys_grads[TIED]}, acc)
    return rows


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

    burn, length = cfg.replay.burn_in, cfg.replay.seq_length
    block = decoder_block(cfg.network)[1]
    sizes = mapper.sizes(block, net.router_trains)
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
    # the forward walks read the ends as `norm` and `lm_head`
    at = reference_on(pair, mapper.untied_view(online),
                      mapper.untied_view(target), items, weights, cfg,
                      sizes, topk_on, topk_tg, greedy, bits)
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
    # the departures hold the same gradient again: a copy on the host
    kept_grads = jax.device_get(sys_grads) if show_limits else None
    # the forward comparison is the host's work and the backward passes
    # the device's: side by side
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
    # the trained positions whose filter reaches into the prefix
    behind = block.conv_L_cache - 1
    unit_behind = _q95(stated["q"][:, :behind], want["q"][:, :behind])[0]
    ok, more_behind = q_behind_the_prefix(sys_q, want["q"], unit_behind,
                                          behind)
    checks["q_behind_the_prefix_matches_reference"] = ok
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
    # the conv operator's own counter: every conv layer passed every
    # position of the prefix and of the trained steps, every sequence
    positions_want = net.num_conv_layers * length * items["obs"].shape[0]
    checks["conv_positions_counter_matches_the_shapes"] = bool(
        float(m["conv_positions"]) == positions_want)
    checks["tree_root_is_leaf_sum"] = correctness.tree_root_is_leaf_sum(
        tree[None])
    valid_want, valid_got = float(want["valid"].mean()), float(
        m["valid_frac"])
    checks["valid_frac_is_the_seeded_share"] = (
        abs(valid_got - valid_want) <= VALID_FRAC_ATOL)
    # with two precisions the rows' third norm repeats the second
    lower_grad = grad_notes.pop("grad_one_bit_less")
    notes = {
        **notes, **more, **more_behind, **grad_notes, "loss_system": got["loss"],
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
        "conv_positions": [float(m["conv_positions"]), positions_want]}
    note("the comparison done")
    if show_limits:
        lower_ok, lower_notes = held_to_reference(
            at[bits[2]], want, stated, compare, w_mean, more["q_unit"])
        lower_ok_behind, lower_behind = q_behind_the_prefix(
            at[bits[2]]["q"], want["q"], unit_behind, behind)
        notes["grad_one_bit_less"] = lower_grad
        notes["one_bit_less"] = {
            "passes": lower_ok and lower_ok_behind and lower_ok_grad,
            **{k: lower_notes[k] for k in (
                "q_err_q95", "priority_err_max_in_q_units", "loss_err",
                "ok")},
            "q_err_q95_behind_the_prefix_in_units": lower_behind[
                "q_err_q95_behind_the_prefix_in_units"]}
        view = mapper.untied_view(online)

        def held_to(changed: dict) -> dict:
            """The system held to the reference at a departure: Q's
            95th percentile in Q's unit over the trained positions,
            `q_behind_the_prefix` as `correct` reads it, and every
            gradient leaf in the unit the comparison proper measured
            for it."""
            sz = sizes._replace(**changed)
            q, _, _, inputs = reference_net(
                pair, view, items["obs"], sz, burn, topk_on,
                FLOAT32_MANTISSA_BITS, keep_inputs=True)
            q_units = _q95(sys_q, q)[0] / max(more["q_unit"], 1e-30)
            ok_behind, at_behind = q_behind_the_prefix(sys_q, q, unit_behind,
                                                       behind)
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
            return {"passes": bool(q_units <= Q_RATIO and ok_behind
                                   and units[worst] <= limits.GRAD_RATIO
                                   and median <= limits.GRAD_MEDIAN_RATIO),
                    "q_err_q95_in_units": q_units,
                    "q_err_q95_behind_the_prefix_in_units": at_behind[
                        "q_err_q95_behind_the_prefix_in_units"],
                    "grad_worst_leaf": [worst, units[worst]],
                    "grad_median_leaf": median}

        for name, changed in (departures or {}).items():
            notes[name] = held_to(changed)
            note(f"departure {name} done")
    return state, checks, notes
