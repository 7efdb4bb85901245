"""`correct` for a decoder SERVED FROM SLOTS, outside the measured
window, at the widths and the lengths the cell runs: what the TIMED
path answered - Q at a session's last decode steps of the window, after
a prefill in chunks and thousands of one-token steps through the slot
state - held to benchmarks/reference/minicpm_sala_q.py's full forward
pass over the session's WHOLE history (prompt and every token decoded),
one layer's weights at a time so that it fits beside the server.

Two rules, and no limit of the first is this file's:

(a) Q, the family's rule with the family's limits
    (token_sequence_checks.Q_RATIO, QUANTILE): 95% of the compared
    Q-values within Q_RATIO units, the unit being the 95th-percentile
    error the reference makes against itself when every value the
    system holds in bfloat16 is rounded to bfloat16's 7 explicit bits.
    The reference runs FORCED to the selections the replies carried
    (every position of the history past `dense_len`, both sparse
    layers): two block scores closer than a rounding fall either way,
    and another block's keys are no rounding error.
(b) the selection's own rule, at the compared positions: with the
    reference's block scores (float32, same forced history) and their
    own error at 7 bits as the unit (the 95th percentile over a query's
    blocks), how far each data-chosen block scores UNDER the
    reference's last chosen score and each block left out ABOVE it,
    summed over a query's blocks and averaged over the compared
    queries, sparse layers and key-value heads, is within SELECT_RATIO
    units. Forced blocks (init, local) must be in the selection
    outright. A sum and a mean, not the worst block: the worst of ~30
    thousand blocks a session has the tail of a maximum (my chip runs,
    PR 55: 0.81-2.10 units over twenty sessions where one bit less
    read 3.10 and 4.08 - no room for a limit), while the number of
    blocks a rounding puts on the wrong side and how far each lands
    both grow with the error, so the sum separates by its square.

`readings` puts every number beside its limit; under the mix's
`show_limits` the same two rules read departures that have to FAIL:
the reference one bit less, the departures the net's mapper lists
(`mapper.DEPARTURES`: MiniCPM-SALA's are `dense_always`, `decay_one`,
`forced_blocks_dropped`, `stale_compressed`) and another session's
answers.

The (reference, mapper) pair is an ARGUMENT: the configuration's file
names both modules (`checks`), the kind imports them and hands them
over. A reference offers `embed` / `block(p, x, sz, kind, forced,
mantissa_bits, score_at)` / `head` over one history and `SPARSE`, the
kind of layer that selects; a mapper `sizes(block_cfg, **departures)`,
`reference_layer(sys_params, index)`, `ends(sys_params)` and
`DEPARTURES` {field of the reference's Sizes: whether the departed
reference chooses its blocks for itself}.

SELECT_RATIO lies between two readings of my chip runs (PR 55, in
PERF.md section 6): the system's `misordered_units` over its seeds and
the reference's own selection at one bit less.
"""

from __future__ import annotations

import functools

import jax
import numpy as np

from benchmarks.harness import correctness
from benchmarks.harness.token_sequence_checks import (
    FLOAT32_MANTISSA_BITS, LOWER_MANTISSA_BITS, Q_RATIO, QUANTILE,
    STATED_MANTISSA_BITS)

SELECT_RATIO = 0.7


@functools.lru_cache(maxsize=None)
def _pieces(ref):
    return (jax.jit(ref.embed, static_argnames=("sz",)),
            jax.jit(ref.block, static_argnames=("sz", "kind")),
            jax.jit(ref.head, static_argnames=("sz",)))


def reference_on(ref, mapper, sys_params: dict, tokens: np.ndarray, sizes,
                 forced, at: np.ndarray, mantissa_bits: int):
    """The reference over one history, a layer at a time. `forced`
    [sparse layers, T, G, topk] or None (its own selection); `at` the
    compared positions. -> (Q [len(at), A], block scores [sparse layers,
    len(at), G, blocks], its own selection there [sparse layers,
    len(at), G, topk])."""
    _embed, _block, _head = _pieces(ref)
    ends = mapper.ends(sys_params)
    x = _embed(ends, tokens, sz=sizes, mantissa_bits=mantissa_bits)
    scores, owns = [], []
    for index, kind in enumerate(sizes.mixer_types):
        sparse = kind == ref.SPARSE
        x, own, score = _block(
            mapper.reference_layer(sys_params, index), x, sz=sizes,
            kind=kind,
            forced=(forced[len(scores)] if sparse and forced is not None
                    else None),
            mantissa_bits=mantissa_bits, score_at=at if sparse else None)
        if sparse:
            scores.append(np.asarray(score))
            owns.append(np.asarray(own[at]))
    q = _head(ends, x[at], sz=sizes, mantissa_bits=mantissa_bits)
    return np.asarray(q), np.stack(scores), np.stack(owns)


def selection_reading(sel: np.ndarray, at: np.ndarray, want: np.ndarray,
                      stated: np.ndarray, sizes) -> dict:
    """sel [sparse layers, len(at), G, topk] against the reference's
    scores `want` (and `stated`, the same at 7 bits: the unit) -> how
    far the selection is out of the reference's order, in units, a
    query, layer and key-value head (`misordered_units`: the rule's
    reading), the worst single block, and whether every forced block
    was chosen."""
    worst, total, cases, forced_ok, units = 0.0, 0.0, 0, True, []
    blocks = want.shape[-1]
    m = np.arange(blocks)
    for layer in range(sel.shape[0]):
        for i, t in enumerate(at):
            if t + 1 <= sizes.dense_len:
                continue
            own = t // sizes.block
            forced = (m < sizes.init_blocks) | (
                (m > own - sizes.window // sizes.block) & (m <= own))
            free = (m <= own) & ~forced
            k = sizes.topk - int(forced.sum())
            for g in range(sel.shape[2]):
                chosen = np.zeros(blocks, bool)
                chosen[sel[layer, i, g][sel[layer, i, g] >= 0]] = True
                forced_ok = forced_ok and bool(chosen[forced].all())
                s, s7 = want[layer, i, g], stated[layer, i, g]
                unit = max(float(np.quantile(np.abs(s7[free] - s[free]),
                                             QUANTILE)), 1e-30)
                last = np.sort(s[free])[-k]
                # a chosen block under the reference's last chosen
                # score, a block left out above it: by how much
                out = np.concatenate([
                    np.maximum(last - s[free & chosen], 0.0),
                    np.maximum(s[free & ~chosen] - last, 0.0)]) / unit
                worst = max(worst, float(out.max(initial=0.0)))
                total += float(out.sum())
                cases += 1
                units.append(unit)
    return {"misordered_units": total / max(cases, 1),
            "worst_block_units": worst, "forced_chosen": forced_ok,
            "score_unit_median": float(np.median(units)) if units else 0.0}


def readings(got_q: np.ndarray, got_sel: np.ndarray, at: np.ndarray,
             want, stated, sizes) -> tuple[bool, dict]:
    """The two rules on one session: got_q [len(at), A], got_sel
    [sparse layers, len(at), G, topk] (None: rule (a) alone); `want`,
    `stated`: `reference_on` at 23 and 7 bits. -> (ok, every reading
    beside its limit)."""
    q_unit = float(np.quantile(np.abs(stated[0] - want[0]), QUANTILE))
    ok_q, q_err = correctness.within_quantile(
        got_q, want[0], Q_RATIO * q_unit, QUANTILE)
    if got_sel is None:     # Q alone
        sel, ok_sel = {}, True
    else:
        sel = selection_reading(got_sel, at, want[1], stated[1], sizes)
        ok_sel = sel["forced_chosen"] and (
            sel["misordered_units"] <= SELECT_RATIO)
    return bool(ok_q and ok_sel), {
        "q_err_units": q_err / max(q_unit, 1e-30), "q_limit": Q_RATIO,
        "q_unit": q_unit, **sel, "select_limit": SELECT_RATIO,
        "ok": {"q": bool(ok_q), "selection": bool(ok_sel)}}


def check_sessions(ref, mapper, sys_params: dict, block_cfg,
                   sessions: list[dict], show_limits: bool = False
                   ) -> tuple[dict, dict]:
    """`ref`, `mapper`: the net's reference and mapper (module
    docstring); `block_cfg`: the net's config block as run
    (`models.decoder_block(cfg.network)[1]`). `sessions`: each {"tokens" [T], "sel" [sparse layers, T, G, topk]
    (what the replies carried; anything where the context is short),
    "at" [n] the compared positions, "q" [n, A] what the timed path
    answered there}. -> (checks, notes)."""
    sizes = mapper.sizes(block_cfg)
    on = functools.partial(reference_on, ref, mapper, sys_params)
    checks, notes = {}, {}
    first = None
    for i, s in enumerate(sessions):
        tokens, at = np.asarray(s["tokens"]), np.asarray(s["at"])
        forced = np.asarray(s["sel"])
        got_sel = forced[:, at]
        want = on(tokens, sizes, forced, at, FLOAT32_MANTISSA_BITS)
        stated = on(tokens, sizes, forced, at, STATED_MANTISSA_BITS)
        ok, note = readings(s["q"], got_sel, at, want, stated, sizes)
        checks[f"session_{i}_matches_reference"] = ok
        notes[f"session_{i}"] = {"positions": int(tokens.shape[0]), **note}
        first = first or (tokens, at, forced, got_sel, want, stated)
    if not show_limits:
        return checks, notes
    # every reading below has to FAIL; the first session carries them
    tokens, at, forced, got_sel, want, stated = first
    got_q = sessions[0]["q"]
    limits = {}
    lower = on(tokens, sizes, None, at, LOWER_MANTISSA_BITS)
    ok, note = readings(lower[0], lower[2], at, want, stated, sizes)
    limits["one_bit_less"] = {"passes": ok, **note}
    # a departure of the selection is seen in the selection: the
    # departed reference chooses for itself (`own`)
    for name, own in mapper.DEPARTURES.items():
        other = mapper.sizes(block_cfg, **{name: True})
        far = on(tokens, other, None if own else forced, at,
                 FLOAT32_MANTISSA_BITS)
        # the unit stays the stated precision's own error, carried over
        # to the departed values (forced blocks' infinities give NaN
        # there, which no rule reads)
        with np.errstate(invalid="ignore"):
            around = (stated[0] - want[0] + far[0],
                      stated[1] - want[1] + far[1])
        ok, note = readings(got_q, got_sel, at, far, around, sizes)
        limits[name] = {"passes": ok, **note}
    if len(sessions) > 1:
        # another slot's state: the second session's answers held to
        # the first one's history (Q alone: its blocks are not these)
        n = min(len(at), len(sessions[1]["at"]))
        ok, note = readings(sessions[1]["q"][:n], None, at[:n],
                            (want[0][:n],), (stated[0][:n],), sizes)
        limits["another_slots_state"] = {"passes": ok, **note}
    notes["show_limits"] = limits
    checks["every_departure_is_refused"] = not any(
        v["passes"] for v in limits.values())
    return checks, notes
