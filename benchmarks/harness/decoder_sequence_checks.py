"""`correct` for a net of the decoder family, outside the measured
window, at the widths and the batch the cell runs: one k=1 draw through
the system's own `sample_k` and `learn_k`, held to a plain reference.
THE GENERAL FORM of afmoe_sequence_checks.py: the reference and the
parameter mapper are ARGUMENTS (`pair = (ref, mapper)`), not names
bound at import, so a further decoder brings its two files and no copy
of this one (benchmarks/README.md has the worked example). Any pair
with afmoe's surface serves - `ref.embed`, `ref.block` (afmoe's
signature: a traced layer index, a static kind),
`ref.head`, `ref.td_loss`, `ref.Sizes` with `layer_types`, `window`,
`first_expert`, `experts_held`; `mapper.sizes`, `.num_layers`,
`.reference_layer`, `.system_layer_gradients` - and
(reference/afmoe_q, harness/afmoe_params) is one. What is model-free
comes from the modules that have it: the rules and limits from
token_sequence_checks.py (its docstring has (a)-(e), the unit - the
error the reference makes against itself at bfloat16's 7 bits - and
the history of every limit), Q's and the loss's rule and the float32
quantile from afmoe_sequence_checks.py (`held_to_reference`, `_q95`).

What differs from that module, and why:

- THE PRIORITIES ARE HELD TO THE REFERENCE'S TD AT THE SYSTEM'S GREEDY
  IDS. afmoe_sequence_checks lets the reference take its own double-Q
  argmax and refuses a correct run about once in 45 (PERF.md section 7:
  where two of the 25,024 Q-values of the online net are tied to
  rounding the reference bootstraps from another action's target value,
  and if that lands on the sequence's largest |TD| the priority reads
  50 units off with nothing wrong). The selection is forced for the
  same reason and the gradient walk already forced the greedy ids; here
  the forward comparison does too (`ref.td_loss(..., greedy=)`), at
  every precision. What the rule then holds is the arithmetic of the
  TD, the n-step sum, the rescaling and eta - what a priority is made
  of - and a wrong argmax in the SYSTEM still shows: Q is compared
  value for value before any argmax is taken. The limit stays
  PRIORITY_UNITS of Q's unit.
- The readings that have to FAIL are a table the caller brings
  (`departures`: name -> fields of `ref.Sizes` to replace) beside the
  two every decoder has, one bit less and the window ignored
  (`window` = the sequence's length). Each departure is the reference
  in float32 at that departure, forced to the system's selection and
  greedy ids, with the system held against it BY TWO OF THE CELL'S
  RULES, and it has to fall to one: Q's (Q_RATIO of Q's unit) and the
  gradient's (worst leaf and median leaf, in the units the comparison
  proper measured for each leaf). Q alone does not do for a share
  without a shared expert: the held experts are an eighth of the
  layer's output, and at random weights a sliding layer averages
  thousands of near-equal values, so SiLU for ReLU moved Q's 95th
  percentile by 1.41 units against a limit of 1.4 and the window by
  1.05 on the v5e (PERF.md section 6, PR 39) - while the experts' own
  gradients, and the keys' and values' of a sliding layer, are another
  function altogether. All run only under `show_limits` and decide
  nothing of `correct`: they are notes that say the limits separate. A
  departure is one walk of the online net forward, the prefix included
  (a layer's output at a trained position depends on the prefix through
  every layer below, so no departure leaves the prefix pass reusable),
  and one backward; the TARGET net's walk is not repeated.
- Nothing is checked at fewer positions than were trained; what is not
  repeated: one compiled graph per KIND of layer and precision-free
  (the bits, the layer index and the window are arguments), the loss
  and the head compiled once.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from ape_x_dqn_tpu.models import decoder_block
from benchmarks.harness import correctness
from benchmarks.harness.afmoe_sequence_checks import (
    _q95, held_to_reference)
from benchmarks.harness.device import say
from benchmarks.harness import token_sequence_checks as limits
from benchmarks.harness.token_sequence_checks import (
    BITS, FLOAT32_MANTISSA_BITS, Q_RATIO, ROWS_RTOL, VALID_FRAC_ATOL,
    _leaf_norms, gradients_match, routing_agrees,
    sequences_are_what_was_written)

LOSS_STATICS = ("n_step", "gamma", "eta", "huber_delta")


def _loss_settings(cfg) -> dict:
    return dict(n_step=cfg.learner.n_step, gamma=cfg.learner.gamma,
                eta=cfg.replay.priority_eta,
                huber_delta=cfg.learner.huber_delta)


def reference_net(pair, sys_params, tokens, sizes, burn_in: int, forced,
                  mantissa_bits: int, keep_inputs: bool = False):
    """The reference on one net's parameters (the system's pytree, read
    in place) at one precision, in blocks so that it fits beside the
    learner's state: one layer's weights at a time, one sequence at a
    time. -> (Q [B, L - burn_in, A], own top-k [layers, B, L, k], gap
    [layers, B, L]) on the host, and with `keep_inputs` each layer's
    input and the head's, [layers + 1][B] arrays [1, L, H], which the
    backward pass starts from (else None)."""
    ref, mapper = pair
    embed = jax.jit(ref.embed)
    block = jax.jit(ref.block, static_argnames=("sz", "burn_in", "kind"))
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
                layer=np.int32(index), kind=sizes.layer_types[index])
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


def reference_on(pair, online, target, items: dict, weights, cfg, sizes,
                 forced_online, forced_target, greedy, bits: tuple) -> dict:
    """`online`/`target`: the system's parameter pytrees; `greedy` [B,
    L - burn_in]: the system's double-Q actions, which the reference's
    loss bootstraps from (the module docstring). -> {bits: {"loss",
    "priorities" [B], "q"/"q_target" [B, L - burn_in, A], "td"/"valid"
    [B, L - burn_in], "topk_*" [layers, B, L, k], "gap_*" [layers, B,
    L], "inputs" (the online net's, see `reference_net`)}} for each
    precision of `bits` (23: the reference proper)."""
    burn = cfg.replay.burn_in
    loss_fn = jax.jit(pair[0].td_loss, static_argnames=LOSS_STATICS)
    trained = [np.asarray(items[k])[:, burn:] for k in (
        "actions", "rewards", "terminals", "mask")]
    weights = np.asarray(weights)
    out = {}
    for m in bits:
        q, own, gap, inputs = reference_net(
            pair, online, items["obs"], sizes, burn, forced_online, m,
            keep_inputs=True)
        q_t, own_t, gap_t, _ = reference_net(
            pair, target, items["obs"], sizes, burn, forced_target, m)
        # one sequence at a time: the loss is a mean over sequences
        losses, parts = [], []
        for b in range(q.shape[0]):
            loss, aux = loss_fn(
                q[b:b + 1], q_t[b:b + 1], *(x[b:b + 1] for x in trained),
                weights[b:b + 1], greedy=greedy[b:b + 1],
                **_loss_settings(cfg))
            losses.append(np.asarray(loss))
            parts.append({k: np.asarray(v) for k, v in aux.items()})
        out[m] = {"loss": float(np.mean(losses, dtype=np.float32)),
                  "q": q, "q_target": q_t, "inputs": inputs,
                  **{k: np.concatenate([p[k] for p in parts])
                     for k in parts[0]},
                  "topk_online": own, "gap_online": gap,
                  "topk_target": own_t, "gap_target": gap_t}
    return out


def gradient_norms(pair, sys_grads: dict, online: dict, at: dict,
                   items: dict, weights, cfg, sizes, forced_online, greedy,
                   bits: tuple = BITS) -> dict:
    """The system's gradient (`sys_grads`, its own pytree, taken apart
    as the walk goes) against `jax.grad` of the reference at the
    precisions `bits` (`BITS`, or its first two: the row's third norm
    is then the second again), leaf by leaf: afmoe_sequence_checks.
    gradient_norms' walk - head, the layers last to first, embedding,
    ONE SEQUENCE AND ONE PIECE ON THE DEVICE AT A TIME, from the layer
    inputs the forward pass kept - for any pair.
    -> {leaf path: `_leaf_norms`}."""
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
    ends = {"final_norm": online["norm"], "head": online["lm_head"]}
    ct = {m: [None] * n for m in bits}
    acc = {}
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
                    sz=sizes, kind=sizes.layer_types[index],
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


def check_learner(pair, learner, net, state, cfg, expected_fn,
                  departures: dict | None = None, show_limits: bool = False,
                  note=say):
    """`pair`: (reference module, mapper module). expected_fn(leaf
    indices [n]) -> the items the seed wrote there. -> (state after the
    k=1 learn step WITHOUT its parameters and optimizer state, checks,
    notes). `show_limits`: also the readings that have to fail, one bit
    less, the window ignored and each of `departures` (the module
    docstring); `note(text)`: told as each part ends.

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

        for name, changed in {
                "window_ignored": {"window": cfg.replay.seq_length},
                **(departures or {})}.items():
            notes[name] = held_to(changed)
            note(f"departure {name} done")
    return state, checks, notes
