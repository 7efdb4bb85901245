"""`correct` for the decoder family's second net (models/afmoe_q.py,
Trinity-Mini), outside the measured window, at the widths and the batch
the cell runs: one k=1 draw through the system's own `sample_k` and
`learn_k`, held to benchmarks/reference/afmoe_q.py. The comparison is
token_sequence_checks.py's, rule for rule - its docstring has (a)-(e),
GRAD_PROGRAM, the order in which room is made, the unit (the error the
reference makes against itself at bfloat16's 7 bits) and the history of
every limit - and its model-free parts are imported from there
(`sequences_are_what_was_written`, `gradients_match`, `routing_agrees`,
`_leaf_norms`, the limits; `matches_reference`'s rules for Q and the
loss are in `held_to_reference`, over float32 where that one copies
307 M values to float64). What else
is written again here is what that module binds to GLM's reference and
mapper by name: the walk over the reference's pieces forward
(`reference_net`, `reference_on`) and backward (`gradient_norms`) and
`check_learner`, bound to this net's pair (`ref`, `mapper`; the two
references and the two mappers have the same functions under the same
names, so the walks are the same text).

One rule more, this model's: A PROGRAM WHOSE SLIDING LAYERS IGNORE THE
WINDOW MUST FAIL. Inside `sliding_window` tokens a windowed and a full
layer agree exactly, so only sequences longer than the window tell them
apart - the cell's 8,192 against 2,048 do. `check_learner(...,
show_limits=True)` also computes the reference's Q-values with the
window taken out (`Sizes.window` = the sequence length; float32, forced
to the system's selection) and holds the system's against them by
Q_RATIO: that has to come out NOT correct, and its reading is in the
notes (`window_ignored`), beside the reading at one bit less.

WHAT A RUN OF THE CELL COMPUTES, AND WHAT ONLY `show_limits` DOES. Both
readings that have to FAIL - the reference at one bit less and the
reference without the window - decide nothing of `correct`: they are
notes that say the limits separate. They cost a third walk over the
reference forward and backward and a seventh forward pass, 8,192
positions each in float32, and the comparison's host arithmetic ran in
float64 over 307 M Q-values five times: with them a run took 780 s on
the v5e (360 s with every graph cached), over the driver's limit for a
run. So a run of the cell walks the reference at the two precisions
that decide (`BITS[:2]`: float32, and bfloat16's 7 bits for the unit),
its quantiles are taken in float32 (`_q95`), and the two demonstrations
run on request; PERF.md section 6 (PR 32) has their readings on the
chip, nine runs, from before the split.

Limits: GLM's, unchanged (token_sequence_checks.py) - the same loss,
the same expert layer and the same unit; PERF.md section 6 (PR 32) has
this net's readings against them on the chip. BUT THE PRIORITIES' RULE
IS THIS CELL'S OWN (`held_to_reference`). GLM's holds three quarters of
16 priorities within 2.5 units, the unit being the 95th percentile of
the 7-bit reference's own 16 priority errors; this cell's batch is TWO
sequences, and a percentile of two values is no unit: over nine runs on
the v5e it read 0.0013 to 0.088 (70 times apart) and the system's two
errors 0.5 to 7.9 of it - two runs of three refused with nothing wrong.
(The 7-bit reference's largest per-step |TD| error is no unit either:
3.3 and 3.8 in two runs, a double-Q flip between near-tied ids of the
25,024 where the two nets have drifted apart; it would hold nothing.)
The unit here is Q's - the 95th percentile of the 7-bit reference's
own error over the batch's 307 M Q-values, the best-determined number
of the comparison (0.0083-0.0085 in every run) - and BOTH priorities
have to lie within PRIORITY_UNITS of it. Why a priority's error is a
few of them: a TD is a Q-value minus h(return + gamma^n h^-1(target
Q)), |h'| <= 1, so about two Q errors; a priority is 0.9 of the
LARGEST |TD| of 6,144 steps, which picks from those errors' tail,
about three times their 95th percentile: 6 units. Read on the v5e,
fourteen runs, 28 priorities: 0.5 to at most 9.2 units (the earlier
runs' notes give the 75th percentile of the two, which bounds the
larger); the reference one bit less 0.6 to 40. PRIORITY_UNITS = 15 is
0.13 on priorities of 2 to 4: an eta of 0.8 for 0.9, a step of the
n-step sum left out or a rescaling forgotten move them by more. It
does not separate one bit less, as GLM's rule does not (Q_RATIO and
the gradient's median leaf do, in every run). One double-Q flip that
lands on a sequence's largest |TD| would refuse a run; none has in 28.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import afmoe_params as mapper
from benchmarks.harness import correctness
from benchmarks.harness.device import say
from benchmarks.harness.token_sequence_checks import (  # noqa: F401
    BITS, FLOAT32_MANTISSA_BITS, LOSS_RATIO, Q_RATIO, QUANTILE, ROWS_RTOL,
    VALID_FRAC_ATOL, _leaf_norms, gradients_match, routing_agrees,
    sequences_are_what_was_written)
from benchmarks.reference import afmoe_q as ref

PRIORITY_UNITS = 15.0       # both priorities, in units of Q's unit


def reference_net(sys_params, tokens, sizes, burn_in: int, forced,
                  mantissa_bits: int, keep_inputs: bool = False,
                  window=None):
    """The reference on one net's parameters (the system's pytree, read
    in place) at one precision, in blocks so that it fits beside the
    learner's state: one layer's weights at a time, one sequence at a
    time. -> (Q [B, L - burn_in, A], own top-k [layers, B, L, k], gap
    [layers, B, L]) on the host, and with `keep_inputs` each layer's
    input and the head's, [layers + 1][B] arrays [1, L, H], which the
    backward pass starts from (else None). `window`: instead of
    `sizes.window` (the sequence's length: the model without it)."""
    # the precision is an argument of the compiled pieces, not a
    # constant in them: one graph per piece serves all three readings
    embed = jax.jit(ref.embed)
    # a layer's index and the window are arguments too: one graph per
    # KIND of layer (dense or expert, sliding or full)
    block = jax.jit(ref.block, static_argnames=("sz", "burn_in", "kind"))
    window = np.int32(sizes.window if window is None else window)
    head = jax.jit(ref.head, static_argnames=("sz",))
    ends = {"embed": sys_params["embed_tokens"],
            "final_norm": sys_params["norm"], "head": sys_params["lm_head"]}
    rows = range(tokens.shape[0])
    x = [embed(ends, tokens[b:b + 1], mantissa_bits=mantissa_bits)
         for b in rows]
    inputs = [[np.asarray(a) for a in x]] if keep_inputs else None
    owns, gaps = [], []
    for index in range(mapper.num_layers(sys_params)):
        p = mapper.reference_layer(sys_params, index)
        routed = "dense" not in p
        own_l, gap_l = [], []
        for b in rows:
            x[b], own, gap = block(
                p, x[b], sz=sizes, burn_in=burn_in,
                forced=forced[len(owns), b:b + 1] if routed else None,
                mantissa_bits=mantissa_bits, tokens=tokens[b:b + 1],
                layer=np.int32(index), kind=sizes.layer_types[index],
                window=window)
            own_l.append(np.asarray(own))
            gap_l.append(np.asarray(gap))
        del p
        if keep_inputs:
            inputs.append([np.asarray(a) for a in x])
        if routed:
            owns.append(np.concatenate(own_l))
            gaps.append(np.concatenate(gap_l))
    q = np.concatenate([np.asarray(head(
        ends, x[b], sz=sizes, mantissa_bits=mantissa_bits)[:, burn_in:])
        for b in rows])
    return q, np.stack(owns), np.stack(gaps), inputs


def reference_on(online, target, items: dict, weights, cfg, sizes,
                 forced_online, forced_target, bits: tuple) -> dict:
    """`online`/`target`: the system's parameter pytrees. -> {bits:
    {"loss", "priorities" [B], "q"/"q_target" [B, L - burn_in, A],
    "td"/"valid" [B, L - burn_in], "topk_*" [layers, B, L, k], "gap_*"
    [layers, B, L], "inputs" (the online net's, see `reference_net`)}}
    for each precision of `bits` (23: the reference proper)."""
    burn = cfg.replay.burn_in
    loss_fn = jax.jit(ref.td_loss, static_argnames=(
        "n_step", "gamma", "eta", "huber_delta"))
    trained = [np.asarray(items[k])[:, burn:] for k in (
        "actions", "rewards", "terminals", "mask")]
    weights = np.asarray(weights)
    out = {}
    for m in bits:
        q, own, gap, inputs = reference_net(
            online, items["obs"], sizes, burn, forced_online, m,
            keep_inputs=True)
        q_t, own_t, gap_t, _ = reference_net(
            target, items["obs"], sizes, burn, forced_target, m)
        # ONE SEQUENCE AT A TIME here too: the loss is a mean over
        # sequences, and both nets' Q-values of the whole batch (2.5 GB)
        # beside the learner's state and the system's gradient would be
        # the most the device holds in a run - `peak_hbm_gib` is to say
        # what the window holds, not what its check does
        losses, parts = [], []
        for b in range(q.shape[0]):
            loss, aux = loss_fn(
                q[b:b + 1], q_t[b:b + 1], *(x[b:b + 1] for x in trained),
                weights[b:b + 1], n_step=cfg.learner.n_step,
                gamma=cfg.learner.gamma, eta=cfg.replay.priority_eta,
                huber_delta=cfg.learner.huber_delta)
            losses.append(np.asarray(loss))
            parts.append({k: np.asarray(v) for k, v in aux.items()})
        out[m] = {"loss": float(np.mean(losses, dtype=np.float32)),
                  "q": q, "q_target": q_t, "inputs": inputs,
                  **{k: np.concatenate([p[k] for p in parts])
                     for k in parts[0]},
                  "topk_online": own, "gap_online": gap,
                  "topk_target": own_t, "gap_target": gap_t}
    return out


def _head_loss(ends, x, q_t, greedy, actions, rewards, terminals, mask,
               weight, *, sz, burn_in: int, n_step: int, gamma: float,
               eta: float, huber_delta: float, batch: int, mantissa_bits):
    """One sequence's share of the batch's loss, from the head's input
    x [1, L, H]: `ref.td_loss` is a mean over sequences. `greedy`: the
    system's double-Q actions (see `ref.td_loss`)."""
    q = ref.head(ends, x, sz, mantissa_bits)[:, burn_in:]
    loss, _ = ref.td_loss(q, q_t, actions, rewards, terminals, mask, weight,
                          n_step=n_step, gamma=gamma, eta=eta,
                          huber_delta=huber_delta, greedy=greedy)
    return loss / batch


def _block_pullback(p, x, ct, forced, tokens, layer, *, sz, burn_in: int,
                    kind: str, mantissa_bits):
    """-> (d loss / d p, d loss / d x) of one layer from the cotangent
    of its output."""
    _, pull = jax.vjp(
        lambda p_, x_: ref.block(p_, x_, sz, burn_in, forced,
                                 mantissa_bits, tokens, layer, kind)[0], p, x)
    return pull(ct)


def _embed_pullback(table, tokens, ct, mantissa_bits):
    _, pull = jax.vjp(
        lambda e: ref.embed({"embed": e}, tokens, mantissa_bits), table)
    return pull(ct)[0]


def gradient_norms(sys_grads: dict, online: dict, at: dict, items: dict,
                   weights, cfg, sizes, forced_online, greedy,
                   bits: tuple = BITS) -> dict:
    """The system's gradient (`sys_grads`, its own pytree) against
    `jax.grad` of the reference at the precisions `bits` (`BITS`, or its
    first two: the row's third norm is then the second again), leaf by
    leaf. The reference's backward pass walks its pieces from the
    loss down - head, the layers last to first, embedding - ONE
    SEQUENCE AT A TIME, the sequences' gradients SUMMED (the loss is a
    mean over sequences), from the layer inputs its forward pass kept
    (`at[bits]["inputs"]`) and forced to the system's selection and to
    its double-Q actions `greedy` [B, L - burn_in]; one piece's
    gradients at a time are on the device, beside the system's.
    -> {leaf path: `_leaf_norms`}."""
    burn, n = cfg.replay.burn_in, items["obs"].shape[0]
    head_grad = jax.jit(
        jax.grad(_head_loss, argnums=(0, 1)),
        static_argnames=("sz", "burn_in", "n_step", "gamma", "eta",
                         "huber_delta", "batch"))
    block_pull = jax.jit(_block_pullback,
                         static_argnames=("sz", "burn_in", "kind"))
    embed_pull = jax.jit(_embed_pullback)
    rows = {}

    def add(total, g):
        # ONE PIECE ON THE DEVICE AT A TIME: a piece's arguments (a
        # sequence's target Q-values are 615 MB) and results are
        # allocated when it is dispatched, so a host that runs ahead of
        # the device holds several pieces' at once, and how far ahead
        # it gets is the machine's business - `peak_hbm_gib` is read
        # after this walk and must not depend on it
        return jax.block_until_ready(
            g if total is None else jax.tree.map(jnp.add, total, g))

    def record(prefix: str, got: dict, per_bits: dict):
        # the same keys on both sides, so the leaves come in one order
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
    ends = {"final_norm": online["norm"], "head": online["lm_head"]}
    ct = {m: [None] * n for m in bits}
    acc = {}
    for m in bits:
        total = None
        for b in range(n):
            g, ct[m][b] = head_grad(
                ends, at[m]["inputs"][-1][b], at[m]["q_target"][b:b + 1],
                greedy[b:b + 1], *(x[b:b + 1] for x in trained),
                weights[b:b + 1],
                sz=sizes, burn_in=burn, n_step=cfg.learner.n_step,
                gamma=cfg.learner.gamma, eta=cfg.replay.priority_eta,
                huber_delta=cfg.learner.huber_delta, batch=n,
                mantissa_bits=m)
            total = add(total, g)
            del g
        acc[m] = {"norm": total["final_norm"], "lm_head": total["head"]}
    record("", {k: sys_grads[k] for k in ("norm", "lm_head")}, acc)

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
                    sz=sizes, burn_in=burn, kind=sizes.layer_types[index],
                    mantissa_bits=m)
                total = add(total, g)
                del g
            acc[m] = mapper.system_layer_gradients(total)
        del p
        record(f"['layers'][{index}]", sys_grads["layers"][index], acc)
        sys_grads["layers"][index] = None       # compared: make room

    for m in bits:
        total = None
        for b in range(n):
            total = add(total, embed_pull(
                online["embed_tokens"], tokens[b:b + 1], ct[m][b], m))
        acc[m] = {"embed_tokens": total}
    record("", {"embed_tokens": sys_grads["embed_tokens"]}, acc)
    return rows


def _q95(a, b, allow: float | None = None):
    """-> (the QUANTILE of |a - b|, the share of it within `allow`) over
    two float32 arrays, in float32: the cell's Q-values are 307 M a
    net, and `correctness.within_quantile`'s float64 copies of them
    took 22 s a quantile on the host where this takes 8 (the difference
    of two float32 values and its quantile's interpolation are exact to
    1e-7 of a unit that is read to 1e-3)."""
    err = np.subtract(np.asarray(a, np.float32), np.asarray(b, np.float32))
    np.abs(err, out=err)
    share = None if allow is None else float(np.mean(err <= allow))
    return float(np.quantile(err, QUANTILE)), share


def held_to_reference(got: dict, want: dict, stated: dict,
                      compare: np.ndarray, weight_mean: float,
                      q_unit: float | None = None) -> tuple[bool, dict]:
    """token_sequence_checks.matches_reference's rules for Q and the
    loss, with Q's quantiles taken by `_q95`, and THIS CELL'S rule for
    the priorities (the module docstring says why): every priority
    drawn once within PRIORITY_UNITS of the comparison's
    best-determined unit, Q's. `q_unit`: the unit, where a caller has
    it already (it is `stated` against `want`, whatever `got` is)."""
    if q_unit is None:
        q_unit = _q95(stated["q"], want["q"])[0]
    finite = bool(np.isfinite(got["q"]).all() and np.isfinite(want["q"]).all())
    q_err, share = _q95(got["q"], want["q"], Q_RATIO * q_unit)
    ok_q = finite and share >= QUANTILE
    trained = want["valid"] > 0
    # the reference's own |TD| error, for the loss's unit
    _, td_unit = correctness.within_quantile(
        stated["td"][trained], want["td"][trained], 0.0, QUANTILE)
    loss_unit = (weight_mean * float(np.abs(want["td"][trained]).mean())
                 * td_unit)
    loss_allow = LOSS_RATIO * loss_unit
    loss_err = abs(got["loss"] - want["loss"])
    ok_loss = bool(np.isfinite(got["loss"]) and loss_err <= loss_allow)
    err = np.abs(np.asarray(got["priorities"], np.float64)[compare]
                 - want["priorities"][compare])
    worst = float(err.max()) if err.size else 0.0
    ok_pri = worst <= PRIORITY_UNITS * q_unit
    return ok_q and ok_pri and ok_loss, {
        "q_err_q95": q_err if finite else float("nan"), "q_unit": q_unit,
        "q_share_within_limit": share,
        "priority_err_max_in_q_units": worst / max(q_unit, 1e-30),
        "loss_err": loss_err, "loss_unit": loss_unit,
        "loss_allow": loss_allow,
        "ok": {"q": ok_q, "priorities": ok_pri, "loss": ok_loss}}


def check_learner(learner, net, state, cfg, expected_fn,
                  show_limits: bool = False, note=say):
    """expected_fn(leaf indices [n]) -> the items the seed wrote there.
    -> (state after the k=1 learn step WITHOUT its parameters and
    optimizer state, checks, notes). `show_limits`: also the two
    readings that have to fail (the module docstring); `note(text)`:
    told as each part ends.

    `learn_k` runs first, on the whole state, and the comparison
    afterwards, on the parameters it started from (kept on the host
    meanwhile): once the step is taken Adam's moments and the updated
    parameters are deleted, and that room is what the gradient program
    (1.9 GiB of gradients + 4.3 of temp at the published widths) and
    the reference's pieces run in."""
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
    sizes = mapper.sizes(cfg.network.afmoe, net.router_trains)
    # what `learn_k` differentiates (`_sgd_update`: the family's loss
    # on the family's batch), compiled apart because the step keeps its
    # gradient to itself; its aux hands back the Q-values and the
    # selections of this very program, and `grad_norm` ties it to the
    # step that was taken
    (sys_loss, aux), sys_grads = jax.jit(jax.value_and_grad(
        learner.family.loss_fn, has_aux=True))(
        online, target, learner.family.make_batch(items), weights)
    sys_q, topk_on, topk_tg = (np.asarray(aux[k]) for k in (
        "q", "topk_online", "topk_target"))
    del aux
    note("the gradient program done")
    at = reference_on(online, target, items, weights, cfg, sizes,
                      topk_on, topk_tg, bits)
    want, stated = at[bits[0]], at[bits[1]]
    note("the reference's forward passes done")
    greedy = sys_q.argmax(axis=-1)
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
    # the forward comparison is the host's work (two quantiles over the
    # Q-values, 20 s at the published widths) and the backward passes
    # the device's: side by side
    with ThreadPoolExecutor(max_workers=1) as beside:
        forward = beside.submit(held_to_reference, got, want, stated,
                                compare, w_mean)
        # takes `sys_grads` apart as it goes
        rows = gradient_norms(sys_grads, online, at, items, weights, cfg,
                              sizes, topk_on, greedy, bits)
        del sys_grads
        note("the reference's backward passes done")
        ok, more = forward.result()
    for entry in at.values():
        del entry["inputs"]
    checks["q_loss_and_priorities_match_reference"] = ok
    ok_grad, lower_ok_grad, grad_notes = gradients_match(
        rows, float(m["grad_norm"]), norm_program)
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
        "moe_load_max_over_mean": float(m["moe_load_max_over_mean"])}
    note("the comparison done")
    if show_limits:
        notes.update(_limits_separate(
            at[bits[2]], want, stated, compare, w_mean, more["q_unit"],
            lower_ok_grad, lower_grad, sys_q,
            # the window taken out of the reference
            lambda: reference_net(
                online, items["obs"], sizes, burn, topk_on,
                FLOAT32_MANTISSA_BITS, window=cfg.replay.seq_length)[0]))
        note("the two readings that have to fail done")
    return state, checks, notes


def _limits_separate(lower, want, stated, compare, w_mean, q_unit,
                     lower_ok_grad, lower_grad, sys_q, q_without_window
                     ) -> dict:
    """The notes of `show_limits`: the reference at one bit less held to
    the comparison's rules, and the system held to the reference
    WITHOUT the window, in the units of that comparison (the reference's
    own error at bfloat16's bits against the reference WITH the
    window). Both have to come out not correct."""
    lower_ok, lower_notes = held_to_reference(lower, want, stated, compare,
                                              w_mean, q_unit)
    no_window_units = _q95(sys_q, q_without_window())[0] / max(q_unit, 1e-30)
    return {
        "grad_one_bit_less": lower_grad,
        "window_ignored": {"passes": no_window_units <= Q_RATIO,
                           "q_err_q95_in_units": no_window_units},
        "one_bit_less": {
            "passes": lower_ok and lower_ok_grad,
            **{k: lower_notes[k] for k in (
                "q_err_q95", "priority_err_max_in_q_units", "loss_err",
                "ok")}}}
