"""`correct` for the decoder family's learner, outside the measured
window, at the widths and the batch the cell runs: one k=1 draw through
the system's own `sample_k` and `learn_k`, held to
benchmarks/reference/glm_moe_q.py. The form is sequence_checks.py's
(its docstring says why errors are measured in units of bfloat16's own
error and not as a share of mean |Q|); what differs is the routing, and
that the backward pass is held too: at these widths it, its
recomputation and the optimizer are most of a step.

(a) the drawn sequences - ids, actions, rewards, terminals, mask - are
    byte for byte what the seed wrote at their indices;
(b) `learn_k` takes its step on the draw. What it differentiates - the
    family's loss on the whole batch of 16 - is then differentiated
    once more here, on the parameters the step started from, because
    the step keeps its gradient to itself (GRAD_PROGRAM below); that
    program's Q-values, the value behind each priority `learn_k`
    wrote, `learn_k`'s loss, and the program's GRADIENT leaf by leaf
    are held to the reference on the same batch, weights and
    parameters, the reference run ONE SEQUENCE AT A TIME (forward and
    backward, the sequences' gradients summed) so that it fits, and
    FORCED to the system's top-k and, in the gradient, to its double-Q
    actions: a near-tie between two scores flips in bfloat16, and a
    flipped expert or bootstrap action moves a token by a whole
    expert's output or another action's value, which is no rounding
    error;
(c) the routing itself: the system's top-k equals the reference's own
    (at the same inputs) wherever the reference's k-th and (k+1)-th
    selection scores differ by more than ROUTING_MARGIN; the share of
    (token, layer) pairs inside the margin is in the notes;
(d) the step's counters `moe_rows` and `moe_rows_grad` are the count
    of the system's selected ids that fall on the experts held here,
    to ROWS_RTOL: the ids are the gradient program's, a second
    compilation of the same passes whose bfloat16 scores differ from
    `learn_k`'s in the last bit, so a few near-ties fall the other way
    (measured: 1 to 16 of 17,000 to 30,000 rows);
(e) the sum-tree's root equals the sum of its leaves, and the
    program's `valid_frac` equals the share the seeded masks give.
(Finite losses and the step counter belong to the traffic kind.)

GRAD_PROGRAM: `jax.value_and_grad(learner.family.loss_fn)` on
`family.make_batch(items)`, the two calls `SingleChipLearner.
_sgd_update` makes; its aux carries the Q-values and the selections of
the same trace (runtime/family.decoder_q_family). It is tied to the
step `learn_k` took by that step's own `grad_norm` (within
GRAD_NORM_RTOL of the program's, or within GRAD_NORM_UNITS units of the
whole gradient's error at bfloat16's 7 bits where that is more: read
0.01-0.6% apart over eleven runs of the second session and 0.01%,
0.06%, 0.1%, 0.5% and 1.6% over five of the third, the last on a batch
whose unit is itself large) and loss. Order and room: `learn_k` runs first on the whole state, with the
parameters it starts from kept on the host; then Adam's moments and the
updated parameters are deleted, and the gradient program (2.2 GiB of
gradients + 2.4 of temp at the published widths) and the reference's
pieces run in that room.

Limits (each between two readings, my chip runs of PR 30, eleven seeds
of the second session, in PERF.md section 6: the system / the reference
at one bit less, in units): the unit is the error the reference makes
against itself when computed with bfloat16's 7 explicit bits of
mantissa (`mantissa_bits=7`, forward activations and backward
cotangents alike) on the same batch, weights and forced choices.
- Q_RATIO: 95% of the trained steps' Q-values within Q_RATIO units
  (0.96-0.97 / 1.96-2.04).
- GRAD_MEDIAN_RATIO, GRAD_RATIO: per leaf of the parameter tree (66-72
  with a gradient) the Euclidean norm of (system's gradient -
  reference's) in units of that leaf's own (reference at 7 bits -
  reference). The MEDIAN leaf within GRAD_MEDIAN_RATIO holds the
  backward pass's precision (0.92-1.12 / 1.35-3.50). The WORST leaf
  within GRAD_RATIO holds that no leaf's gradient is wrong. This
  comparison found one that was: while a block's recomputation decided
  the selection again, the worst leaf - always a stack of expert
  matrices - read 0.97-2.28 over seven seeds and 5.3, 10.7 and 12.7
  over three more (16-19% of the leaf's norm, where few rows were
  routed here); with the selection kept (models/glm_moe_q.py) the same
  seed that read 10.7 reads 1.29, its expert stacks 1.1 (ONE run: the
  chip budget ended there). One bit less reads 1.9-6.0 at the worst
  leaf; a backward rule wrong by a factor of two (the combine's
  cotangent halved, at tiny widths) reads 60: a unit is about 2% of a
  leaf's norm. A leaf to which the reference gives no gradient (the
  selection bias; in a share without the exchange, the router; an
  expert stack no trained row reached) has none in the system.
- PRIORITY_RATIO, PRIORITY_QUANTILE: three quarters of the priorities
  `learn_k` wrote (leaves drawn once), in |delta| space, within
  PRIORITY_RATIO units (the unit is the 95th percentile of the 7-bit
  reference's own priority error; the readings in the notes are the
  75th percentile of the system's, `priority_err_q75`). A priority is
  0.9 of the LARGEST |delta| of 384 steps; one token whose selection
  fell the other way in `learn_k` than in the program the reference is
  forced to (see (d)), or one double-Q argmax that flips between
  near-tied ids, moves it by a whole expert's output or by the gap
  between two Q-values, which is no rounding error. At the 95th
  percentile of 16 values one such token decides the run: eight runs
  read 0.35-1.63 units there and a ninth 3.78. What this rule is for -
  the loss's arithmetic end to end: target, n-step sum, mask,
  rescaling, eta mix, write-back at the right leaves - is wrong in
  every sequence or in none, so three quarters hold it; it does not
  separate one bit less (that is Q_RATIO's and GRAD_MEDIAN_RATIO's to
  do): it reads 0.05-0.74 / 0.09-2.47 at the 75th percentile.
- LOSS_RATIO: the loss within LOSS_RATIO of sequence_checks.py's loss
  unit (0.005-0.38 / 0.01-0.62: it holds the arithmetic; the precision
  is Q_RATIO's to hold).
Every run also makes the same comparison on the reference at ONE bit
of mantissa less (`mantissa_bits=6`), which has to come out not
correct; its readings are in the notes.

Under the cell's forced balanced selection (third session, five seeds,
system / one bit less): Q 0.965-0.969 / 1.98-2.02; median leaf
0.89-1.08 / 0.61-4.85 and worst leaf 1.07-1.56 / 1.67-9.27 (in one run
of five the gradient limits do not separate one bit less - its unit
was itself large - and Q_RATIO does in all five); priorities
0.09-0.73 at the 75th percentile; loss 0.002-0.075; no selection
differs from the reference's own and the rows counters are exact.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import correctness, glm_params
from benchmarks.harness import token_content as tc
from benchmarks.reference import glm_moe_q as ref

FLOAT32_MANTISSA_BITS = 23  # nothing rounded: the reference proper
STATED_MANTISSA_BITS = 7    # bfloat16: `sizes.compute_dtype`
LOWER_MANTISSA_BITS = 6     # one bit less: has to come out not correct
Q_RATIO = 1.4
PRIORITY_RATIO = 2.5
LOSS_RATIO = 2.0
QUANTILE = correctness.QUANTILE
PRIORITY_QUANTILE = 0.75
ROUTING_MARGIN = 0.03       # in selection-score units (sigmoid + bias; under
# the cell's forced selection scores are integers and none is inside it)
ROWS_RTOL = 0.005           # the step's counter against a second forward
GRAD_RATIO = 4.0            # worst leaf, in units of bfloat16's own error
GRAD_MEDIAN_RATIO = 1.3     # the median leaf, in the same units
GRAD_NORM_RTOL = 0.02       # learn_k's grad_norm against the compared one,
GRAD_NORM_UNITS = 2.5       # or this many units of the whole gradient, if more
VALID_FRAC_ATOL = 1e-6


def sequences_are_what_was_written(items: dict, expected: dict
                                   ) -> tuple[bool, dict]:
    """Byte-exact over every leaf of the drawn items, and no leaf
    beyond them (no state entry rides with a token sequence)."""
    wrong = {}
    for k in tc.ITEM_KEYS:
        got, want = np.asarray(items[k]), np.asarray(expected[k])
        differ = (got != want).reshape(got.shape[0], -1).any(axis=1)
        wrong[k] = int(differ.sum()) if (
            got.shape == want.shape and got.dtype == want.dtype) else -1
    extra = sorted(set(items) - set(tc.ITEM_KEYS))
    return not any(wrong.values()) and not extra, {
        "sequences_wrong": wrong, "extra_leaves": extra}


def reference_net(sys_params, tokens, sizes, burn_in: int, forced,
                  mantissa_bits: int, keep_inputs: bool = False):
    """The reference on one net's parameters (the system's pytree, read
    in place) at one precision, in blocks so that it fits beside the
    learner's state: one layer's weights at a time, one sequence at a
    time. -> (Q [B, L - burn_in, A], own top-k [layers, B, L, k], gap
    [layers, B, L]) on the host, and with `keep_inputs` each layer's
    input and the head's, [layers + 1][B] arrays [1, L, H], which the
    backward pass starts from (else None)."""
    # the precision is an argument of the compiled pieces, not a
    # constant in them: one graph per piece serves all three readings
    embed = jax.jit(ref.embed)
    block = jax.jit(ref.block, static_argnames=("sz", "burn_in", "layer"))
    head = jax.jit(ref.head, static_argnames=("sz",))
    ends = {"embed": sys_params["embed_tokens"],
            "final_norm": sys_params["norm"], "head": sys_params["lm_head"]}
    rows = range(tokens.shape[0])
    x = [embed(ends, tokens[b:b + 1], mantissa_bits=mantissa_bits)
         for b in rows]
    inputs = [[np.asarray(a) for a in x]] if keep_inputs else None
    owns, gaps = [], []
    for index in range(glm_params.num_layers(sys_params)):
        p = glm_params.reference_layer(sys_params, index)
        routed = "dense" not in p
        own_l, gap_l = [], []
        for b in rows:
            x[b], own, gap = block(
                p, x[b], sz=sizes, burn_in=burn_in,
                forced=forced[len(owns), b:b + 1] if routed else None,
                mantissa_bits=mantissa_bits, tokens=tokens[b:b + 1],
                layer=index)
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
    out = {}
    for m in bits:
        q, own, gap, inputs = reference_net(
            online, items["obs"], sizes, burn, forced_online, m,
            keep_inputs=True)
        q_t, own_t, gap_t, _ = reference_net(
            target, items["obs"], sizes, burn, forced_target, m)
        loss, aux = loss_fn(
            q, q_t, *(items[k][:, burn:] for k in (
                "actions", "rewards", "terminals", "mask")),
            np.asarray(weights), n_step=cfg.learner.n_step,
            gamma=cfg.learner.gamma, eta=cfg.replay.priority_eta,
            huber_delta=cfg.learner.huber_delta)
        out[m] = {"loss": float(loss), "q": q, "q_target": q_t,
                  "inputs": inputs,
                  **{k: np.asarray(v) for k, v in aux.items()},
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


def _block_pullback(p, x, ct, forced, tokens, *, sz, burn_in: int,
                    layer: int, mantissa_bits):
    """-> (d loss / d p, d loss / d x) of one layer from the cotangent
    of its output."""
    _, pull = jax.vjp(
        lambda p_, x_: ref.block(p_, x_, sz, burn_in, forced,
                                 mantissa_bits, tokens, layer)[0], p, x)
    return pull(ct)


def _embed_pullback(table, tokens, ct, mantissa_bits):
    _, pull = jax.vjp(
        lambda e: ref.embed({"embed": e}, tokens, mantissa_bits), table)
    return pull(ct)[0]


@jax.jit
def _leaf_norms(got, want, stated, lower):
    """-> [|got - want|, |stated - want|, |lower - want|, |want|,
    |got|], Euclidean norms over one leaf."""
    norm = lambda a: jnp.sqrt(jnp.sum(jnp.square(     # noqa: E731
        a.astype(jnp.float32))))
    return jnp.stack([norm(got - want), norm(stated - want),
                      norm(lower - want), norm(want), norm(got)])


BITS = (FLOAT32_MANTISSA_BITS, STATED_MANTISSA_BITS, LOWER_MANTISSA_BITS)


def gradient_norms(sys_grads: dict, online: dict, at: dict, items: dict,
                   weights, cfg, sizes, forced_online, greedy) -> dict:
    """The system's gradient (`sys_grads`, its own pytree) against
    `jax.grad` of the reference at the three precisions of `BITS`, leaf
    by leaf. The reference's backward pass walks its pieces from the
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
                         static_argnames=("sz", "burn_in", "layer"))
    embed_pull = jax.jit(_embed_pullback)
    rows = {}

    def add(total, g):
        return g if total is None else jax.tree.map(jnp.add, total, g)

    def record(prefix: str, got: dict, per_bits: dict):
        # the same keys on both sides, so the leaves come in one order
        others = [jax.tree.leaves(per_bits[m]) for m in BITS]
        flat = jax.tree_util.tree_flatten_with_path(got)[0]
        for i, (path, leaf) in enumerate(flat):
            rows[prefix + jax.tree_util.keystr(path)] = np.asarray(
                _leaf_norms(leaf, *(o[i] for o in others)))

    trained = [np.asarray(items[k])[:, burn:] for k in (
        "actions", "rewards", "terminals", "mask")]
    tokens = np.asarray(items["obs"])
    weights = np.asarray(weights)
    ends = {"final_norm": online["norm"], "head": online["lm_head"]}
    ct = {m: [None] * n for m in BITS}
    acc = {}
    for m in BITS:
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
        acc[m] = {"norm": total["final_norm"], "lm_head": total["head"]}
    record("", {k: sys_grads[k] for k in ("norm", "lm_head")}, acc)

    layers = glm_params.num_layers(online)
    routed_before = np.cumsum([0] + [
        "experts" in online["layers"][i]["mlp"] for i in range(layers)])
    for index in reversed(range(layers)):
        p = glm_params.reference_layer(online, index)
        routed = "dense" not in p
        for m in BITS:
            total = None
            for b in range(n):
                g, ct[m][b] = block_pull(
                    p, at[m]["inputs"][index][b], ct[m][b],
                    (forced_online[routed_before[index], b:b + 1]
                     if routed else None), tokens[b:b + 1],
                    sz=sizes, burn_in=burn, layer=index, mantissa_bits=m)
                total = add(total, g)
            acc[m] = glm_params.system_layer_gradients(total)
        del p
        record(f"['layers'][{index}]", sys_grads["layers"][index], acc)
        sys_grads["layers"][index] = None       # compared: make room

    for m in BITS:
        total = None
        for b in range(n):
            total = add(total, embed_pull(
                online["embed_tokens"], tokens[b:b + 1], ct[m][b], m))
        acc[m] = {"embed_tokens": total}
    record("", {"embed_tokens": sys_grads["embed_tokens"]}, acc)
    return rows


def gradients_match(rows: dict, norm_learn_k: float, norm_program: float
                    ) -> tuple[bool, bool, dict]:
    """-> (the system's gradient matches, the reference at one bit less
    would, notes). Per leaf, in units of the error the reference at the
    stated precision makes on that leaf: the WORST leaf within
    GRAD_RATIO and the MEDIAN leaf within GRAD_MEDIAN_RATIO (the module
    docstring says what each is for); a leaf the reference gives no
    gradient (the selection bias; in a share without the exchange, the
    router) has none in the system; and the norm of the gradient
    `learn_k` itself reported within GRAD_NORM_RTOL of this one's, or
    within GRAD_NORM_UNITS units of the whole gradient's own error if
    that is more, which is what ties the comparison to the step that
    was taken."""
    system, lower, silent_ok, silent = {}, {}, True, []
    for path, (err, unit, err_lower, want, got) in rows.items():
        if want == 0.0:
            silent.append(path)
            silent_ok = silent_ok and got == 0.0
            continue
        system[path] = float(err / max(unit, 1e-30))
        lower[path] = float(err_lower / max(unit, 1e-30))
    worst = max(system, key=system.get)
    worst_lower = max(lower, key=lower.get)
    median = float(np.median(list(system.values())))
    median_lower = float(np.median(list(lower.values())))
    # two sound bfloat16 gradients lie each about a unit from the true
    # one, so their norms differ by at most the sum of the two; where a
    # batch's unit is large (a double-Q argmax or a Huber corner that
    # falls the other way under rounding moves the whole gradient) the
    # two compilations differ by as much, which is no fault of a step
    unit_all = float(np.sqrt(sum(r[1] ** 2 for r in rows.values())))
    apart = abs(norm_learn_k - norm_program)
    tied = apart <= max(GRAD_NORM_RTOL * norm_program,
                        GRAD_NORM_UNITS * unit_all)
    ok = bool(silent_ok and tied and np.isfinite(system[worst])
              and system[worst] <= GRAD_RATIO
              and median <= GRAD_MEDIAN_RATIO)
    lower_ok = bool(lower[worst_lower] <= GRAD_RATIO
                    and median_lower <= GRAD_MEDIAN_RATIO)
    return ok, lower_ok, {
        "grad_worst_leaf": [worst, system[worst]],
        "grad_median_leaf": median,
        "grad_worst_leaf_relative_error": float(
            rows[worst][0] / rows[worst][3]),
        "grad_leaves_compared": len(system),
        "grad_leaves_without_gradient": len(silent),
        "grad_norm_learn_k_and_program": [norm_learn_k, norm_program],
        "grad_norm_apart_in_units": apart / max(unit_all, 1e-30),
        "grad_unit_share_of_norm": unit_all / max(norm_program, 1e-30),
        "grad_one_bit_less": {
            "worst_leaf": [worst_lower, lower[worst_lower]],
            "least_leaf": min(lower.values()),
            "median_leaf": median_lower, "passes": lower_ok},
        "ok_grad": {"worst_leaf": bool(system[worst] <= GRAD_RATIO),
                    "median_leaf": median <= GRAD_MEDIAN_RATIO,
                    "silent_leaves": silent_ok, "tied_to_learn_k": tied}}


def matches_reference(got: dict, want: dict, stated: dict,
                      compare: np.ndarray, weight_mean: float
                      ) -> tuple[bool, dict]:
    """sequence_checks.matches_reference with this family's limits:
    `got` against `want` (the float32 reference) in units of the error
    `stated` (the reference at the stated precision) makes."""
    def q95(a, b):
        return float(np.quantile(np.abs(np.asarray(a, np.float64) - b),
                                 QUANTILE))

    trained = want["valid"] > 0
    q_unit = q95(stated["q"], want["q"])
    pri_unit = q95(stated["priorities"][compare],
                   want["priorities"][compare])
    loss_unit = (weight_mean * float(np.abs(want["td"][trained]).mean())
                 * q95(stated["td"][trained], want["td"][trained]))
    ok_q, q_err = correctness.within_quantile(
        got["q"], want["q"], Q_RATIO * q_unit, QUANTILE)
    ok_pri, pri_err = correctness.within_quantile(
        got["priorities"][compare], want["priorities"][compare],
        PRIORITY_RATIO * pri_unit, PRIORITY_QUANTILE)
    loss_allow = LOSS_RATIO * loss_unit
    loss_err = abs(got["loss"] - want["loss"])
    ok_loss = bool(np.isfinite(got["loss"]) and loss_err <= loss_allow)
    return ok_q and ok_pri and ok_loss, {
        "q_err_q95": q_err, "q_unit": q_unit,
        "priority_err_q75": pri_err, "priority_unit": pri_unit,
        "loss_err": loss_err, "loss_unit": loss_unit,
        "loss_allow": loss_allow,
        "ok": {"q": ok_q, "priorities": ok_pri, "loss": ok_loss}}


def routing_agrees(system_topk, own_topk, gap) -> tuple[bool, dict]:
    """Sets of selected ids per (layer, sequence, position): equal
    wherever the reference's gap is above the margin."""
    same = (np.sort(system_topk, axis=-1)
            == np.sort(own_topk, axis=-1)).all(axis=-1)
    decided = gap > ROUTING_MARGIN
    differ = ~same
    return bool((same | ~decided).all()), {
        "inside_margin_share": float(1.0 - decided.mean()),
        "selections_differing_share": float(differ.mean()),
        "largest_gap_of_a_differing_selection": float(
            gap[differ].max()) if differ.any() else 0.0}


def check_learner(learner, net, state, cfg, expected_fn):
    """expected_fn(leaf indices [n]) -> the items the seed wrote there.
    -> (state after the k=1 learn step WITHOUT its parameters and
    optimizer state, checks, notes).

    `learn_k` runs first, on the whole state, and the comparison
    afterwards, on the parameters it started from (kept on the host
    meanwhile): once the step is taken Adam's moments and the updated
    parameters are deleted, and that room is what the gradient program
    (2.2 GiB of gradients + 2.4 of temp at the published widths) and
    the reference's pieces run in."""
    sample, rng = learner.sample_k(state, 1)
    items = jax.tree.map(lambda x: np.asarray(x)[0], sample[0])
    idx = np.asarray(sample[1]).reshape(-1).astype(np.int64)
    weights = np.asarray(sample[2])[0]
    ok, notes = sequences_are_what_was_written(items, expected_fn(idx))
    checks = {"sequences_are_what_was_written": ok}

    before = jax.device_get(state.params)
    state, m = learner.learn_k(state._replace(rng=rng), sample, 1)
    m = jax.device_get(m)
    tree = np.asarray(state.replay.tree)
    for x in jax.tree.leaves((state.params, state.opt_state)):
        x.delete()
    state = state._replace(params=None, opt_state=None)
    # a target sync at this very step would have changed them
    assert int(state.step) % cfg.learner.target_sync_every
    online, target = jax.device_put(before), state.target_params
    del before

    glm, burn = cfg.network.glm, cfg.replay.burn_in
    sizes = glm_params.sizes(glm, net.router_trains)
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
    at = reference_on(online, target, items, weights, cfg, sizes,
                      topk_on, topk_tg, BITS)
    want, stated, lower = (at[b] for b in BITS)
    greedy = sys_q.argmax(axis=-1)
    norm_program = float(np.sqrt(sum(
        float(jnp.vdot(g, g)) for g in jax.tree.leaves(sys_grads))))
    # takes `sys_grads` apart as it goes
    rows = gradient_norms(sys_grads, online, at, items, weights, cfg, sizes,
                          topk_on, greedy)
    del sys_grads
    for entry in at.values():
        del entry["inputs"]

    cap = tree.shape[0] // 2
    compare = correctness.drawn_once(idx)
    w_mean = float(np.mean(weights))
    # back from the stored (p + eps)^alpha to the priority in |delta|
    # space
    sys_pri = np.maximum(np.asarray(tree[cap + idx], np.float64), 0.0) ** (
        1.0 / cfg.replay.alpha) - cfg.replay.eps
    got = {"q": sys_q, "priorities": sys_pri, "loss": float(m["loss"])}
    ok, more = matches_reference(got, want, stated, compare, w_mean)
    lower_ok, lower_notes = matches_reference(lower, want, stated, compare,
                                              w_mean)
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
    return state, checks, {
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
        "one_bit_less": {
            "passes": lower_ok and lower_ok_grad,
            **{k: lower_notes[k] for k in (
                "q_err_q95", "priority_err_q75", "loss_err", "ok")}}}
