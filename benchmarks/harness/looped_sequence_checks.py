"""`correct` for a net of the decoder family WITHOUT an expert layer
whose blocks are applied several times a forward pass
(models/ouro_q.py), outside the measured window, at the widths and the
batch the cell runs: one k=1 draw through the system's own `sample_k`
and `learn_k`, held to a plain reference. decoder_sequence_checks.py's
counterpart: that module's walk stacks per-layer expert selections and
visits a layer once; this one has no selection to force and VISITS
EVERY LAYER ONCE PER LOOP STEP, forward and backward, so the walk is
its own and everything model-free is imported - the rules and limits
from token_sequence_checks.py (its docstring has (a)-(e), the unit -
the error the reference makes against itself at bfloat16's 7 bits - and
the history of every limit; `gradients_match`, `_leaf_norms`,
`sequences_are_what_was_written`), Q's, the loss's and the priorities'
rule and the float32 quantile from afmoe_sequence_checks.py
(`held_to_reference`, `_q95`), the loss's settings from
decoder_sequence_checks.py. The pair (reference, mapper) is an argument
as there, so a further looped decoder brings its two files
(benchmarks/README_looped_cell.md).

What is held, and how it fits beside the learner's state:

- Q value for value (95% of the trained steps' 3,072 x 49,152 values
  within Q_RATIO units), the loss (LOSS_RATIO of its unit), every
  priority drawn once (PRIORITY_UNITS of Q's unit, at the SYSTEM's
  greedy ids: decoder_sequence_checks.py's docstring says why), and the
  gradient LEAF BY LEAF (worst leaf within GRAD_RATIO, median leaf
  within GRAD_MEDIAN_RATIO, in units of the error the reference at the
  stated precision makes on that leaf).
- THE GRADIENT OF A LOOPED WEIGHT IS HELD TO THE REFERENCE'S SUM OVER
  ITS APPLICATIONS: the system's scan hands back one leaf per weight;
  the reference's backward walk visits (last step, last layer) ..
  (first step, first layer), pulls the cotangent through one block
  application at a time and ADDS the parameter cotangents of a layer's
  `loop_steps` visits (and the final norm's, once a step). One
  application's weights and one sequence on the device at a time, from
  the block inputs the forward walk kept on the host ([steps, layers]
  arrays [1, L, H] a sequence).
- THE MEDIAN LEAF HAS THIS CELL'S OWN LIMIT, GRAD_MEDIAN_RATIO = 2.0
  (the sibling cells': 1.3), set in PR 41's first session from
  readings that no longer stand. The batch is ONE sequence: the error
  the forward pass leaves in the TD errors is pulled back through every
  leaf, so inside a run every leaf but the q / k projections reads ONE
  number - (the system's TD-error draw) / (the 7-bit reference's) - and
  the median over leaves is that draw again. While the net left its
  roundings to `astype` that draw read 0.42-1.53 over thirty-three
  seeds and the reference at one bit less 0.72-3.19 over seven: the
  two overlapped, no limit lay between them, at 1.3 a correct run
  would have been refused about one time in twenty, and 2.0 was the
  room such a draw needs (Q's rule refused one bit less: 1.57-2.12
  against 1.4). THE SPREAD WAS NOT THE BATCH'S BUT XLA'S: roundings
  taken back where the reader converts to float32 again, apart in the
  forward pass and in the recomputation (models/ouro_q.py `_held`;
  PERF.md section 6). With the net's roundings held the system reads
  0.92-1.01 over six seeds (one bit less 2.02) and 1.3 would hold
  again; the limit stands at 2.0 until a PR has the seeds to lower it
  (PERF.md section 7).
  Taking each error's component along the reference's gradient out
  before the median was tried in the first session and NOT kept. The
  worst leaf (GRAD_RATIO 4.0), Q, the loss and the priorities keep the
  family's limits.
- THE WORST LEAF WAS A q OR k PROJECTION EVERY TIME (no q/k norms:
  their gradients are 1e-4 of v_proj's, and the 7-bit reference's own
  error on them is 7-109% of the leaf's norm). With SmallThinker's
  `about_mean` the system read 1.38-2.41 over seventeen seeds and 4.87
  on the driver's 513284178, which refused the PR; with the kernel's
  `recompute_delta` (a probe on that draw found delta = out . d_out)
  1.09-3.44 over sixteen, layer 0's k_proj in the last loop steps;
  with the net's roundings held as well 1.06-1.21 over six, no leaf
  apart from the others (PERF.md section 6 has the whole account).
- The exit gate has no counterpart in the reference (it is no part of
  Q): its gradient has to be exactly zero, which `gradients_match`
  holds as a leaf "without gradient".
- the step's counters: `loop_block_applications` = the configuration's
  loop steps x layers, exactly; `loop_exit_mass_last` inside [0, 1].

The readings that have to FAIL (`show_limits`; notes that decide
nothing of `correct`): one mantissa bit less, and each of the caller's
`departures` (name -> fields of `ref.Sizes` to replace: fewer loop
steps, step t reading step 0's prefix, the final norm once after the
loop, no post-sublayer norms), each the reference in float32 at that
departure with the system held against it BY TWO OF THE CELL'S RULES,
Q's and the gradient's (worst and median leaf, in the units the
comparison proper measured), and it has to fall to one. A departure is
one walk of the online net forward and one backward; the target net's
walk is not repeated.
"""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from ape_x_dqn_tpu.models import decoder_block
from benchmarks.harness import correctness
from benchmarks.harness import token_sequence_checks as limits
from benchmarks.harness.afmoe_sequence_checks import _q95, held_to_reference
from benchmarks.harness.decoder_sequence_checks import (
    LOSS_STATICS, _loss_settings)
from benchmarks.harness.device import say
from benchmarks.harness.token_sequence_checks import (
    BITS, FLOAT32_MANTISSA_BITS, Q_RATIO, VALID_FRAC_ATOL, _leaf_norms,
    gradients_match, sequences_are_what_was_written)

GRAD_MEDIAN_RATIO = 2.0     # this cell's own: the module docstring


def _ends(sys_params: dict) -> dict:
    return {"embed": sys_params["embed_tokens"],
            "final_norm": sys_params["norm"], "head": sys_params["lm_head"]}


def reference_net(pair, sys_params, tokens, sizes, burn_in: int,
                  mantissa_bits: int, keep_inputs: bool = False):
    """The reference on one net's parameters (the system's pytree, read
    in place) at one precision: a Python loop over the loop steps and
    the layers, one block application's weights and one sequence at a
    time. -> (Q [B, L - burn_in, A] on the host; with `keep_inputs`
    what the backward walk starts from, else None: {"blocks" [steps]
    [layers][B] the block applications' inputs [1, L, H], "ends"
    [steps][B] the inputs of each step's closing norm, "head" [B] the
    head's, "prefix" {(layer, b): step 0's (k, v) at the burn-in
    positions} under the `step_0` departure})."""
    ref, mapper = pair
    embed = jax.jit(ref.embed)
    block = jax.jit(ref.block, static_argnames=("sz", "burn_in"))
    end = jax.jit(ref.end_of_step, static_argnames=("sz", "step"))
    head = jax.jit(ref.head, static_argnames=("sz",))
    ends = _ends(sys_params)
    rows = range(tokens.shape[0])
    layers = mapper.num_layers(sys_params)
    shared = sizes.prefix_from == "step_0"
    x = [embed(ends, tokens[b:b + 1], mantissa_bits=mantissa_bits)
         for b in rows]
    kept = {"blocks": [], "ends": [], "prefix": {}}
    for step in range(sizes.loop_steps):
        kept["blocks"].append([])
        for index in range(layers):
            p = mapper.reference_layer(sys_params, index)
            kept["blocks"][step].append([np.asarray(a) for a in x]
                                        if keep_inputs else None)
            for b in rows:
                x[b], kv = block(
                    p, x[b], sz=sizes, burn_in=burn_in,
                    mantissa_bits=mantissa_bits,
                    prefix=kept["prefix"].get((index, b)))
                if shared and step == 0:
                    kept["prefix"][index, b] = kv
            del p
        kept["ends"].append([np.asarray(a) for a in x]
                            if keep_inputs else None)
        x = [end(ends, x[b], sz=sizes, step=step,
                 mantissa_bits=mantissa_bits) for b in rows]
    kept["head"] = [np.asarray(a) for a in x]
    q = np.concatenate([np.asarray(head(
        ends, x[b], sz=sizes, mantissa_bits=mantissa_bits)[:, burn_in:])
        for b in rows])
    return q, (kept if keep_inputs else None)


def reference_on(pair, online, target, items: dict, weights, cfg, sizes,
                 greedy, bits: tuple) -> dict:
    """`online`/`target`: the system's parameter pytrees; `greedy` [B,
    L - burn_in]: the system's double-Q actions, which the reference's
    loss bootstraps from. -> {bits: {"loss", "priorities" [B],
    "q"/"q_target" [B, L - burn_in, A], "td"/"valid" [B, L - burn_in],
    "inputs" (the online net's, see `reference_net`)}} for each
    precision of `bits` (23: the reference proper)."""
    burn = cfg.replay.burn_in
    loss_fn = jax.jit(pair[0].td_loss, static_argnames=LOSS_STATICS)
    trained = [np.asarray(items[k])[:, burn:] for k in (
        "actions", "rewards", "terminals", "mask")]
    weights = np.asarray(weights)
    out = {}
    for m in bits:
        q, inputs = reference_net(pair, online, items["obs"], sizes, burn,
                                  m, keep_inputs=True)
        q_t, _ = reference_net(pair, target, items["obs"], sizes, burn, m)
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
                     for k in parts[0]}}
    return out


@functools.cache
def _pullbacks(ref, burn: int, n: int):
    """The backward walk's four compiled pieces for one reference, a
    burn-in and a batch of n: the precision is an argument, so one
    compilation serves every precision and departure of a `Sizes`."""
    def head_loss(ends, x, q_t, greedy, actions, rewards, terminals, mask,
                  weight, m, *, sz, **settings):
        q = ref.head(ends, x, sz, m)[:, burn:]
        return ref.td_loss(q, q_t, actions, rewards, terminals, mask,
                           weight, greedy=greedy, **settings)[0] / n

    def block_pullback(p, x, ct, prefix, m, *, sz):
        return jax.vjp(lambda p_, x_: ref.block(
            p_, x_, sz, burn, m, prefix)[0], p, x)[1](ct)

    def end_pullback(gain, x, ct, m, *, sz, step):
        return jax.vjp(lambda g_, x_: ref.end_of_step(
            {"final_norm": g_}, x_, sz, step, m), gain, x)[1](ct)

    def embed_pullback(table, tokens, ct, m):
        return jax.vjp(lambda e: ref.embed({"embed": e}, tokens, m),
                       table)[1](ct)[0]

    return (jax.jit(jax.grad(head_loss, argnums=(0, 1)),
                    static_argnames=("sz",) + LOSS_STATICS),
            jax.jit(block_pullback, static_argnames=("sz",)),
            jax.jit(end_pullback, static_argnames=("sz", "step")),
            jax.jit(embed_pullback))


def reference_gradient(pair, online: dict, at: dict, items: dict, weights,
                       cfg, sizes, greedy, mantissa_bits: int) -> dict:
    """`jax.grad` of the reference at one precision as the system's
    pytree (on the host), from the inputs the forward walk kept
    (`at["inputs"]`, `at["q_target"]`): head, then the loop steps last
    to first and inside each the layers last to first, then the
    embedding - the parameter cotangents of a layer's visits ADDED UP,
    one application and one sequence on the device at a time."""
    ref, mapper = pair
    burn, n = cfg.replay.burn_in, items["obs"].shape[0]
    m = np.int32(mantissa_bits)
    head_grad, block_pull, end_pull, embed_pull = _pullbacks(ref, burn, n)

    def add(total, g):
        # fenced: a host that runs ahead of the device would hold
        # several pieces' arguments and results at once
        return jax.block_until_ready(
            g if total is None else jax.tree.map(jnp.add, total, g))

    trained = [np.asarray(items[k])[:, burn:] for k in (
        "actions", "rewards", "terminals", "mask")]
    tokens = np.asarray(items["obs"])
    weights = np.asarray(weights)
    inputs = at["inputs"]
    ct, head_total = [None] * n, None
    for b in range(n):
        g, ct[b] = head_grad(
            {"head": online["lm_head"]}, inputs["head"][b],
            at["q_target"][b:b + 1], greedy[b:b + 1],
            *(x[b:b + 1] for x in trained), weights[b:b + 1], m, sz=sizes,
            **_loss_settings(cfg))
        head_total = add(head_total, g["head"])
    layers = mapper.num_layers(online)
    norm_total, layer_total = None, [None] * layers
    for step in reversed(range(sizes.loop_steps)):
        for b in range(n):
            g, ct[b] = end_pull(online["norm"], inputs["ends"][step][b],
                                ct[b], m, sz=sizes, step=step)
            norm_total = add(norm_total, g)
        for index in reversed(range(layers)):
            p = mapper.reference_layer(online, index)
            for b in range(n):
                g, ct[b] = block_pull(
                    p, inputs["blocks"][step][index][b], ct[b],
                    inputs["prefix"].get((index, b)) if step else None,
                    m, sz=sizes)
                layer_total[index] = add(layer_total[index], g)
                del g
            del p
    embed_total = None
    for b in range(n):
        embed_total = add(embed_total, embed_pull(
            online["embed_tokens"], tokens[b:b + 1], ct[b], m))
    return jax.device_get(mapper.system_gradients(
        {"embed": embed_total, "layers": layer_total,
         "final_norm": norm_total, "head": head_total},
        online[mapper.GATE]))


def gradient_norms(pair, sys_grads: dict, online: dict, at: dict,
                   items: dict, weights, cfg, sizes, greedy,
                   bits: tuple = BITS) -> dict:
    """The system's gradient (its own pytree, on the host or the
    device) against the reference's at the precisions `bits` (`BITS`,
    or a prefix of it: the row's missing norms repeat the last), leaf
    by leaf. -> {leaf path: `_leaf_norms`}."""
    others = [jax.tree.leaves(reference_gradient(
        pair, online, at[m], items, weights, cfg, sizes, greedy, m))
        for m in bits]
    others += others[-1:] * (len(BITS) - len(bits))
    rows = {}
    flat = jax.tree_util.tree_flatten_with_path(sys_grads)[0]
    for i, (path, leaf) in enumerate(flat):
        rows[jax.tree_util.keystr(path)] = np.asarray(
            _leaf_norms(leaf, *(o[i] for o in others)))
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

    block_cfg = decoder_block(cfg.network)[1]
    sizes = mapper.sizes(block_cfg)
    # what `learn_k` differentiates, compiled apart because the step
    # keeps its gradient to itself; its aux hands back the Q-values of
    # this very program, and `grad_norm` ties it to the step that was
    # taken
    (sys_loss, aux), sys_grads = jax.jit(jax.value_and_grad(
        learner.family.loss_fn, has_aux=True))(
        online, target, learner.family.make_batch(items), weights)
    sys_q = np.asarray(aux["q"])
    del aux
    # the comparison reads the gradient leaf by leaf: on the host, so
    # that the device holds the reference's pieces alone
    sys_grads = jax.device_get(sys_grads)
    note("the gradient program done")
    greedy = sys_q.argmax(axis=-1)
    at = reference_on(pair, online, target, items, weights, cfg, sizes,
                      greedy, bits)
    want, stated = at[bits[0]], at[bits[1]]
    note("the reference's forward passes done")
    norm_program = float(np.sqrt(sum(
        float(np.vdot(g, g)) for g in jax.tree.leaves(sys_grads))))
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
    with ThreadPoolExecutor(max_workers=1) as beside:
        forward = beside.submit(held_to_reference, got, want, stated,
                                compare, w_mean)
        rows = gradient_norms(pair, sys_grads, online, at, items, weights,
                              cfg, sizes, greedy, bits)
        note("the reference's backward passes done")
        ok, more = forward.result()
    for entry in at.values():
        del entry["inputs"]
    checks["q_loss_and_priorities_match_reference"] = ok
    _, _, grad_notes = gradients_match(
        rows, float(m["grad_norm"]), norm_program)
    # the family's rules with this cell's limit for the median leaf
    lower_grad = grad_notes.pop("grad_one_bit_less")
    grad_notes["ok_grad"]["median_leaf"] = (
        grad_notes["grad_median_leaf"] <= GRAD_MEDIAN_RATIO)
    ok_grad = all(grad_notes["ok_grad"].values())
    lower_ok_grad = lower_grad["passes"] = bool(
        lower_grad["worst_leaf"][1] <= limits.GRAD_RATIO
        and lower_grad["median_leaf"] <= GRAD_MEDIAN_RATIO)
    # every leaf, not the worst alone: [its error in units of the stated
    # precision's own, the reference's norm]
    note("gradient leaves " + repr({
        path: [round(float(r[0] / max(r[1], 1e-30)), 2), float(r[3])]
        for path, r in rows.items()}))
    checks["gradients_match_reference"] = ok_grad
    applications_want = block_cfg.total_ut_steps * block_cfg.num_hidden_layers
    exit_mass = float(m["loop_exit_mass_last"])
    checks["loop_counters_match_configuration"] = bool(
        float(m["loop_block_applications"]) == applications_want
        and 0.0 <= exit_mass <= 1.0)
    checks["tree_root_is_leaf_sum"] = correctness.tree_root_is_leaf_sum(
        tree[None])
    valid_want, valid_got = float(want["valid"].mean()), float(
        m["valid_frac"])
    checks["valid_frac_is_the_seeded_share"] = (
        abs(valid_got - valid_want) <= VALID_FRAC_ATOL)
    # (with two precisions the rows' third norm repeats the second)
    notes = {
        **notes, **more, **grad_notes, "loss_system": got["loss"],
        "loss_reference": want["loss"],
        "loss_of_the_gradient_program": float(sys_loss),
        "q_abs_mean": float(np.abs(want["q"]).mean()),
        "weight_mean": w_mean,
        "priorities_compared": int(compare.sum()),
        "valid_share_reference": valid_want,
        "valid_frac_system": valid_got,
        "loop_block_applications": [float(m["loop_block_applications"]),
                                    applications_want],
        "loop_exit_mass_last": exit_mass}
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
            q, inputs = reference_net(
                pair, online, items["obs"], sz, cfg.replay.burn_in,
                FLOAT32_MANTISSA_BITS, keep_inputs=True)
            q_units = _q95(sys_q, q)[0] / max(more["q_unit"], 1e-30)
            apart = gradient_norms(
                pair, sys_grads, online, {FLOAT32_MANTISSA_BITS: {
                    "inputs": inputs, "q_target": want["q_target"]}},
                items, weights, cfg, sz, greedy,
                bits=(FLOAT32_MANTISSA_BITS,))
            units = {path: float(apart[path][0] / max(r[1], 1e-30))
                     for path, r in rows.items() if r[3] != 0.0}
            worst = max(units, key=units.get)
            median = float(np.median(list(units.values())))
            return {"passes": bool(q_units <= Q_RATIO
                                   and units[worst] <= limits.GRAD_RATIO
                                   and median <= GRAD_MEDIAN_RATIO),
                    "q_err_q95_in_units": q_units,
                    "grad_worst_leaf": [worst, units[worst]],
                    "grad_median_leaf": median}

        for name, changed in (departures or {}).items():
            notes[name] = held_to(changed)
            note(f"departure {name} done")
    return state, checks, notes
