"""`correct` for a decoder served from slots THAT SELECTS NOTHING, outside
the measured window, at the widths and the lengths the cell runs: what
the TIMED path answered and what it LEFT ON THE DEVICE, held to the
net's reference's full forward pass over the session's WHOLE history
(prompt and every token decoded), one layer's weights at a time so that
it fits beside the server. slot_session_checks.py's sibling for a net
whose state is a recurrence (benchmarks/README_state_slot_cell.md says
why it stands beside it).

THREE rules, each on what one of them alone can see:

- Q, the family's rule with the family's limits
  (token_sequence_checks.Q_RATIO, QUANTILE and the three precisions,
  imported): of the Q-values at a session's last decode steps of the
  window - after a prefill in chunks and hundreds of one-token steps
  through the slot state - 95% within Q_RATIO units, the unit being the
  95th-percentile error the reference makes against itself when every
  value the system holds in bfloat16 is rounded to bfloat16's 7 explicit
  bits.
- STATE: the recurrence's state the server holds of the session when
  the run stops (`mapper.device_state`: every recurrent layer's h,
  float32), against the reference's h after the same history, float32
  against float32: IN EVERY LAYER the norm of the error within
  `STATE_RATIO` units, a layer's unit being the norm of the error the
  reference's h makes there against itself at bfloat16's 7 bits (what
  rounding the layer's inputs costs; the state itself stays float32 at
  every precision). What Q cannot see of the part the configuration
  states at float32: a carry kept in bfloat16 moves Q by what one more
  rounding of y does, and h by several units - most in the first
  layers, whose inputs have gathered the least rounding, which is why
  each layer is held in its own unit and the worst one is the reading.
- KEYS: the program counter `mapper.KEYS_ATTENDED`, which the net counts
  FROM THE MASKS ITS ATTENTION APPLIED, equals the reference's
  `keys_attended` of every session's length, to the key. One key of
  thousands moves no Q; it moves this count by one a query.

`readings` puts every number beside its limit; under the mix's
`show_limits` the same rules read departures that have to FAIL, each by
at least one rule: the reference one bit less, the departures the net's
mapper lists (`mapper.DEPARTURES`: {field of the reference's Sizes: the
value that departs}, `mapper.FROM_FIRST_COMPARED` standing for the first
compared position) and another session's answers and state.

The (reference, mapper) pair is an ARGUMENT: the configuration's file
names both modules (`checks`), the kind imports them and hands them
over. A reference offers `embed` / `block_and_state(p, x, sz, kind,
mantissa_bits)` / `head` over one history, `keys_attended(lengths, sz)`
and a `Sizes` with `kinds` (one a layer) and one field per departure; a
mapper `sizes(block_cfg, **departures)`, `reference_layer(sys_params,
index)`, `ends(sys_params)`, `device_state(slot_state, slot)`,
`DEPARTURES` and `KEYS_ATTENDED`.
"""

from __future__ import annotations

import functools

import jax
import numpy as np

from benchmarks.harness import correctness
from benchmarks.harness.token_sequence_checks import (
    FLOAT32_MANTISSA_BITS, LOWER_MANTISSA_BITS, Q_RATIO, QUANTILE,
    STATED_MANTISSA_BITS)

# the STATE rule's limit, in a layer's own units (module docstring),
# between two chip readings (PERF.md section 6, PR 57): a sound run's
# worst layer read 1.04-1.15 over 20 sessions of ten seeds, and the
# reference with its carry rounded to bfloat16 after every position
# 6.43 and 12.38 on two seeds' shortest sessions (1,554 and 1,540
# positions; in the first Mamba layers, whose inputs are the least
# rounded - the deepest read 1.2-1.6, which is why the worst layer is
# the reading)
STATE_RATIO = 2.5


@functools.lru_cache(maxsize=None)
def _pieces(ref):
    return (jax.jit(ref.embed, static_argnames=("sz",)),
            jax.jit(ref.block_and_state, static_argnames=("sz", "kind")),
            jax.jit(ref.head, static_argnames=("sz",)))


def reference_on(ref, mapper, sys_params: dict, tokens: np.ndarray, sizes,
                 at: np.ndarray, mantissa_bits: int
                 ) -> tuple[np.ndarray, np.ndarray]:
    """The reference over one history, a layer at a time -> (Q [len(at),
    A] at the compared positions `at`, h [recurrent layers, ...] after
    the last position)."""
    _embed, _block, _head = _pieces(ref)
    ends = mapper.ends(sys_params)
    x = _embed(ends, tokens, sz=sizes, mantissa_bits=mantissa_bits)
    states = []
    for index, kind in enumerate(sizes.kinds):
        x, h = _block(mapper.reference_layer(sys_params, index), x, sz=sizes,
                      kind=kind, mantissa_bits=mantissa_bits)
        if h is not None:
            states.append(np.asarray(h))
    return np.asarray(_head(ends, x[at], sz=sizes,
                            mantissa_bits=mantissa_bits)), np.stack(states)


def readings(got_q: np.ndarray, want: np.ndarray, stated: np.ndarray
             ) -> tuple[bool, dict]:
    """The Q rule on one session: got_q [n, A] against the reference at
    23 bits (`want`), the unit from the reference at 7 (`stated`) ->
    (ok, every reading beside its limit)."""
    q_unit = float(np.quantile(np.abs(stated - want), QUANTILE))
    ok, q_err = correctness.within_quantile(got_q, want, Q_RATIO * q_unit,
                                            QUANTILE)
    return bool(ok), {"q_err_units": q_err / max(q_unit, 1e-30),
                      "q_limit": Q_RATIO, "q_unit": q_unit}


def state_readings(got_h: np.ndarray, want: np.ndarray, stated: np.ndarray
                   ) -> tuple[bool, dict]:
    """The state rule on one session: got_h [layers, ...] float32
    against the reference's h at 23 bits (`want`), each layer's unit
    from the reference at 7 (`stated`) -> (ok, the worst layer's reading
    beside its limit)."""
    def norms(a):
        return np.sqrt(np.square(np.asarray(a, np.float64)).reshape(
            len(a), -1).sum(axis=1))

    units = norms(got_h - want) / np.maximum(norms(stated - want), 1e-30)
    worst = int(np.argmax(np.nan_to_num(units, nan=np.inf)))
    ok = bool(np.isfinite(units).all() and units[worst] <= STATE_RATIO)
    return ok, {"state_err_units": float(units[worst]),
                "state_limit": STATE_RATIO, "state_worst_layer": worst,
                "state_median_units": float(np.median(units))}


def _both(got: tuple, want: tuple, stated: tuple) -> tuple[bool, dict]:
    """The Q rule and the state rule on one session: each argument a
    (Q, h) pair -> (both hold, their readings)."""
    ok_q, note_q = readings(got[0], want[0], stated[0])
    ok_h, note_h = state_readings(got[1], want[1], stated[1])
    return ok_q and ok_h, {"q_ok": ok_q, "state_ok": ok_h, **note_q,
                           **note_h}


def check_sessions(ref, mapper, sys_params: dict, block_cfg,
                   sessions: list[dict], lengths, counters: dict,
                   show_limits: bool = False) -> tuple[dict, dict]:
    """`ref`, `mapper`: the net's reference and mapper (module
    docstring); `block_cfg`: the net's config block as run
    (`models.decoder_block(cfg.network)[1]`). `sessions`: each {"tokens"
    [T] everything the session sent, "at" [n] the compared positions,
    "q" [n, A] what the timed path answered there, "state" what
    `mapper.device_state` read of its slot after the last token};
    `lengths`: every session's positions on the device, `counters`: the
    server's, over the whole run. -> (checks, notes)."""
    sizes = mapper.sizes(block_cfg)
    on = functools.partial(reference_on, ref, mapper, sys_params)
    checks, notes = {}, {}
    first = None
    for i, s in enumerate(sessions):
        tokens, at = np.asarray(s["tokens"]), np.asarray(s["at"])
        want = on(tokens, sizes, at, FLOAT32_MANTISSA_BITS)
        stated = on(tokens, sizes, at, STATED_MANTISSA_BITS)
        _, note = _both((s["q"], s["state"]), want, stated)
        checks[f"session_{i}_matches_reference"] = note.pop("q_ok")
        checks[f"session_{i}_state_matches_reference"] = note.pop("state_ok")
        notes[f"session_{i}"] = {"positions": int(tokens.shape[0]), **note}
        first = first or (tokens, at, want, stated)
    counted = int(counters.get(mapper.KEYS_ATTENDED, -1))
    attended = ref.keys_attended(lengths, sizes)
    checks["keys_attended_are_the_masks"] = counted == attended
    notes["keys_attended"] = {"counted": counted, "reference": attended}
    if not show_limits:
        return checks, notes
    # every reading below has to FAIL by one rule at least; the first
    # session carries them
    tokens, at, want, stated = first
    got = sessions[0]["q"], sessions[0]["state"]
    limits = {}
    ok, note = _both(on(tokens, sizes, at, LOWER_MANTISSA_BITS), want, stated)
    limits["one_bit_less"] = {"passes": ok, **note}
    for name, value in mapper.DEPARTURES.items():
        if value == getattr(mapper, "FROM_FIRST_COMPARED", None):
            value = int(at.min())
        departed = mapper.sizes(block_cfg, **{name: value})
        far = on(tokens, departed, at, FLOAT32_MANTISSA_BITS)
        # the units stay the stated precision's own errors, carried over
        # to the departed values
        ok, note = _both(got, far, tuple(
            s - w + f for s, w, f in zip(stated, want, far)))
        keys_ok = counted == ref.keys_attended(lengths, departed)
        limits[name] = {"passes": ok and keys_ok, "keys_ok": keys_ok, **note}
    if len(sessions) > 1:
        # another slot's state: the second session's answers and state
        # held to the first one's history
        n = min(len(at), len(sessions[1]["at"]))
        ok, note = _both((sessions[1]["q"][:n], sessions[1]["state"]),
                         (want[0][:n], want[1]), (stated[0][:n], stated[1]))
        limits["another_slots_state"] = {"passes": ok, **note}
    notes["show_limits"] = limits
    checks["every_departure_is_refused"] = not any(
        v["passes"] for v in limits.values())
    return checks, notes
