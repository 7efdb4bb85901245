"""`scope_stats` on a small recorded trace. data/scope_probe.xplane.pb
(88 kB) was recorded on the v5e in PR 26 from five calls of

    def f(x, w):                               # x [256, 512], w [512, 512]
        with jax.named_scope("r2d2.torso"):
            y = jnp.tanh(x @ w)
        def body(c, _):
            with jax.named_scope("inner"):
                return jnp.tanh(c @ w), None
        with jax.named_scope("r2d2.lstm_scan"):
            y, _ = jax.lax.scan(body, y, None, length=8)
        return (y ** 2).sum()
    jax.jit(jax.value_and_grad(f, argnums=1))

so it has a scope around plain ops, a scope around a `while` (forward
and backward: `jvp(...)` and `transpose(jvp(...))` in the name stack),
a scope inside the loop body, and ops under none."""

import os

from benchmarks.harness import scope_stats, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PROBE = os.path.join(DATA, "scope_probe.xplane.pb")
# a trace whose metadata was stripped to hlo_category and source
NO_NAME_STACKS = os.path.join(DATA, "pong_live_100ms.xplane.pb")


def test_name_stacks_are_read_off_the_wire():
    stacks = scope_stats.op_name_stacks(PROBE)["/device:TPU:0"]
    by_scope = {}
    for text, stack in stacks.items():
        name, _ = trace_reduce.op_key(text)
        by_scope.setdefault(stack, []).append(name)
    assert by_scope["jit(f)/jvp(r2d2.torso)/dot_general:"] == [
        "convolution_tanh_fusion.1"]
    assert by_scope["jit(f)/transpose(jvp(r2d2.torso))/dot_general:"] == [
        "convolution_add_fusion.1"]
    assert any("transpose(jvp(r2d2.lstm_scan))/while/body" in s
               for s in by_scope)


def test_scope_times_on_the_recorded_trace():
    got = scope_stats.scope_times(
        PROBE, ("r2d2.torso", "r2d2.lstm_scan", "inner", "no.such.scope"))
    assert got == {"r2d2.torso": 26054, "r2d2.lstm_scan": 118646,
                   "inner": 108342, "no.such.scope": 0}
    # against the op table trace_reduce makes of the same file: the
    # torso scope is exactly its two fusions, forward and backward
    ops = trace_reduce.reduce(PROBE)["devices"][0]["op_ns"]
    ns = {label.split(" ")[0]: v for label, v in ops.items()}
    assert got["r2d2.torso"] == (ns["convolution_tanh_fusion.1"]
                                 + ns["convolution_add_fusion.1"])
    # the scan scope is its body's ops plus the two loops' own time,
    # which carry no name stack and inherit it from what runs in them
    body = sum(ns[k] for k in (
        "convolution_add_fusion.3", "fusion.24",
        "bitcast_dynamic-update-slice_fusion.5",
        "bitcast_dynamic-update-slice_fusion.6"))
    loops = ns["while.5"] + ns["while.6"]
    assert 0 < loops < 0.02 * body
    assert body < got["r2d2.lstm_scan"] <= body + loops + 8000
    busy = trace_reduce.reduce(PROBE)["devices"][0]["busy_ns"]
    assert got["r2d2.torso"] + got["r2d2.lstm_scan"] <= busy


def test_a_loop_inherits_a_scope_only_from_nearly_all_of_its_body():
    s = ("a",)
    # [0, 100): a loop with no name stack; 95 of its 96 nested ns are "a"
    events = [(0, 100, None), (0, 95, "x/a/y"), (95, 96, "x/b")]
    assert scope_stats._scope_self_times(events, s) == {"a": 95 + 4}
    # half under "a": the loop's own 4 ns are nobody's
    events = [(0, 100, None), (0, 48, "x/a/y"), (48, 96, "x/b")]
    assert scope_stats._scope_self_times(events, s) == {"a": 48}
    # an outer loop over an inherited inner loop and as much other work
    events = [(0, 210, None), (0, 100, None), (0, 96, "a"),
              (100, 200, "b")]
    assert scope_stats._scope_self_times(events, s) == {"a": 100}


def test_a_trace_without_name_stacks_gives_nothing_to_read():
    assert scope_stats.scope_times(NO_NAME_STACKS) == {}

    class Rt:
        @staticmethod
        def newest_xplane():
            return NO_NAME_STACKS

    facts = {"runtime": Rt, "trace": {"devices": [{"busy_ns": 1000}]}}
    assert scope_stats.share_of_busy(facts, "r2d2.lstm_scan") is None
    assert facts["scope_ns"] == {}
