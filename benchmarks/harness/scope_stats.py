"""Device self time by `jax.named_scope`, out of an `.xplane.pb`.

The profiler writes the JAX name stack of the instruction an HLO op was
made from as the stat `tf_op` in the op's event metadata, e.g.
`jit(train_many)/while/body/closed_call/transpose(jvp(r2d2.lstm_scan))/
while/body/dot_general:` (seen on the v5e, PR 26) - forward ops carry
`jvp(<scope>)`, backward ops `transpose(jvp(<scope>))`, so "the name
appears in the stack" covers both. `xplane_meta.py` reads two other
stats off the same wire format and takes no argument for a third, so
this module walks the metadata itself with that module's helpers.

An op is *under* scope S if S is in its `tf_op`. A `while` op carries
no `tf_op`; its self time (the loop's own sequencing between the ops
of its body) counts under S if at least INHERIT_SHARE of the time
nested in it is under S - the scan of an LSTM is under the scope its
cell's ops are under, the train loop around everything is under none.
Times are self times clipped to the harness's `bench.trace_window`,
first chip, like `trace_reduce`'s categories: a scope's share of
`busy_ns` is comparable with theirs. A trace whose ops carry no
`tf_op` (or a program with no such scope, as the parent of the PR that
adds one) gives an empty table and the readers return nothing.
"""

from __future__ import annotations

import json

from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.device import say
from benchmarks.harness.xplane_meta import (_BYTES, _VARINT, _fields,
                                            _map_values, _text)

STAT = "tf_op"
INHERIT_SHARE = 0.9
# every scope a reader asks for; one pass over the trace serves all
SCOPES = ("r2d2.burn_in", "r2d2.unroll", "r2d2.torso", "r2d2.lstm_scan",
          "r2d2.head", "r2d2.stack_rebuild", "replay.sample_gather")


def op_name_stacks(path: str) -> dict[str, dict[str, str]]:
    """-> {plane name: {op text: name stack}} for every op whose event
    metadata has the `tf_op` stat."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out: dict[str, dict[str, str]] = {}
    for number, wire, plane in _fields(space):
        if number != 1 or wire != _BYTES:
            continue
        name, events, stat_names = "", [], {}
        for f, w, value in _fields(plane):
            if f == 2 and w == _BYTES:
                name = _text(value)
            elif f == 4 and w == _BYTES:
                events.append(_map_values(value))
            elif f == 5 and w == _BYTES:
                sid, sname = 0, ""
                for g, _, v in _fields(_map_values(value)):
                    if g == 1:
                        sid = v
                    elif g == 2:
                        sname = _text(v)
                stat_names[sid] = sname
        wanted = {i for i, n in stat_names.items() if n == STAT}
        ops: dict[str, str] = {}
        for meta in events:
            text, stack = "", None
            for f, w, value in _fields(meta):
                if f == 2 and w == _BYTES:
                    text = _text(value)
                elif f == 5 and w == _BYTES:
                    sid, got = 0, None
                    for g, gw, v in _fields(value):
                        if g == 1:
                            sid = v
                        elif g == 5 and gw == _BYTES:
                            got = _text(v)
                        elif g == 7 and gw == _VARINT:
                            got = stat_names.get(v, "")
                    if sid in wanted and got is not None:
                        stack = got
            if text and stack:
                ops[text] = stack
        if ops:
            out[name] = ops
    return out


def _scope_self_times(events: list[tuple[int, int, str | None]],
                      scopes: tuple[str, ...]) -> dict[str, int]:
    """(start, end, name stack or None) of one line's nested events ->
    self time under each scope."""
    out = {s: 0 for s in scopes}
    # [end, name stack, duration, nested ns, nested ns under each scope]
    stack: list[list] = []

    def close(until: float) -> None:
        while stack and stack[-1][0] <= until:
            _, names, dur, nested, under = stack.pop()
            self_ns = max(dur - nested, 0)
            for s in scopes:
                if names is not None:
                    inside = s in names
                else:
                    inside = nested > 0 and under[s] >= INHERIT_SHARE * nested
                if inside:
                    out[s] += self_ns
                    under[s] = dur      # all of it, for the parent
            if stack:
                stack[-1][3] += dur
                for s in scopes:
                    stack[-1][4][s] += under[s]

    for lo, hi, names in sorted(events, key=lambda e: (e[0], -e[1])):
        close(lo)
        stack.append([hi, names, hi - lo, 0, {s: 0 for s in scopes}])
    close(float("inf"))
    return out


def scope_times(path: str, scopes: tuple[str, ...] = SCOPES
                ) -> dict[str, int]:
    """-> {scope: self ns under it} on the first chip, inside the traced
    window; {} when no op of that chip carries a name stack."""
    from jax.profiler import ProfileData

    stacks = op_name_stacks(path)
    data = ProfileData.from_file(path)
    window = tr._host_annotations(data).get(tr.WINDOW_ANNOTATION)
    for plane in data.planes:
        if not plane.name.startswith(tr.DEVICE_PLANE_PREFIX):
            continue
        names = stacks.get(plane.name)
        if not names:
            return {}
        for line in plane.lines:
            if line.name != tr.OPS_LINE:
                continue
            events = [(lo, hi, names.get(ev.name))
                      for ev, lo, hi in tr._events(line)]
            if window:
                lo, hi = window[0]
                events = [(max(a, lo), min(b, hi), n)
                          for a, b, n in events if b > lo and a < hi]
            return _scope_self_times(events, scopes)
    return {}


def of(facts: dict) -> dict[str, int]:
    """The run's scope table, computed once per result line; its
    shares of busy time go to stderr beside the harness's `trace` line
    (scopes nest: `r2d2.burn_in` contains a torso, a scan and a head)."""
    if "scope_ns" not in facts:
        path = facts["runtime"].newest_xplane()
        facts["scope_ns"] = scope_times(path) if path else {}
        busy = max(facts["trace"]["devices"][0]["busy_ns"], 1)
        say("scopes_% " + json.dumps({
            s: round(100.0 * ns / busy, 2)
            for s, ns in facts["scope_ns"].items()}))
    return facts["scope_ns"]


def share_of_busy(facts: dict, scope: str) -> float | None:
    """Self time under `scope` / busy time, first chip, in %."""
    busy = facts["trace"]["devices"][0]["busy_ns"]
    ns = of(facts).get(scope)
    if not ns or not busy:
        return None
    return 100.0 * ns / busy
