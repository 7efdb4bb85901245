"""From a profiler trace (`.xplane.pb`) to device busy/idle time, the
op table and idle-gap attribution, with nothing but
`jax.profiler.ProfileData`.

What a TPU trace holds (looked at by hand, PR 22): one plane per chip
named `/device:TPU:<n>` whose line `XLA Ops` has one event per executed
HLO op and whose line `XLA Modules` has one event per executed program
(`jit_train_many(...)`); host threads are lines of the `/host:CPU`
plane, where `jax.profiler.TraceAnnotation` spans appear under their
own names. All planes share one clock (nanoseconds).

An op event's name is the whole HLO instruction (`%fusion.12 = ...
fusion(...), kind=kOutput, calls=...`); the table keys it by the part
before ` = `. Ops nest: a `%while` event spans the ops of its body.
XLA's `hlo_category` of each op and the program `source` line that
made it sit in the plane's event metadata, which `ProfileData` does
not expose; benchmarks/harness/xplane_meta.py reads them off the wire.

- busy: the union of the `XLA Ops` intervals of a chip, clipped to the
  traced window; idle share = 1 - busy / window. A chip that waits on
  memory inside a running program counts as busy.
- op time: self time, an op's duration minus that of the ops nested in
  it, so a loop does not count its body twice.
- window: the harness's own `bench.trace_window` annotation when the
  trace has it, else first event start to last event end.
- idle gaps: the holes in that union, each attributed to the `bench.*`
  host annotation that overlaps it most (the harness wraps its own
  calls into the layers); holes nothing covers are `unattributed`.
"""

from __future__ import annotations

import os
import re
from collections import defaultdict

from benchmarks.harness import xplane_meta

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE_PREFIX = "/host:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_ANNOTATION = "bench.trace_window"
ANNOTATION_PREFIX = "bench."
# gaps shorter than this are launch latency between back-to-back ops,
# not the host holding the chip back
MIN_GAP_NS = 20_000
COLLECTIVE_OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
                      "collective-permute", "all-to-all")
# hlo_category values of ops that run on the MXU
MXU_CATEGORIES = ("convolution", "convolution fusion", "matmul",
                  "matmul fusion", "dot", "output fusion")
_OP_TEXT = re.compile(r"^%?(?P<name>\S+) = .*? (?P<opcode>[\w\-]+)\(")


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[tuple[int, int]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            if hi > merged[-1][1]:
                merged[-1] = (merged[-1][0], hi)
        else:
            merged.append((lo, hi))
    return merged


def _clip(intervals, lo: int, hi: int):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _length(intervals) -> int:
    return sum(b - a for a, b in intervals)


def _events(line):
    for ev in line.events:
        start = int(ev.start_ns)
        yield ev, start, start + int(ev.duration_ns)


def op_key(text: str) -> tuple[str, str]:
    """HLO instruction text -> (short name, opcode); async pairs fold
    onto their base opcode (`all-reduce-start` -> `all-reduce`)."""
    m = _OP_TEXT.match(text)
    if not m:
        return text.split(" = ")[0].lstrip("%")[:80], "unknown"
    opcode = m.group("opcode")
    for suffix in ("-start", "-done"):
        if opcode.endswith(suffix):
            opcode = opcode[:-len(suffix)]
    return m.group("name")[:80], opcode


def _label(name: str, meta: dict) -> str:
    """`fusion.2098 [custom fusion] frame_ring.py:388`: what a reader
    of the breakdown needs to find the op in the program."""
    parts = [name]
    if meta.get("hlo_category"):
        parts.append(f"[{meta['hlo_category']}]")
    if meta.get("source"):
        parts.append(os.path.basename(meta["source"]))
    return " ".join(parts)


def _self_times(events: list[tuple[int, int, str]]) -> dict[str, int]:
    """(start, end, key) of one line's nested events -> self time per
    key: duration minus the durations of directly nested events."""
    out: dict[str, int] = defaultdict(int)
    stack: list[list] = []          # [end, key, duration, nested]

    def close(until: float) -> None:
        while stack and stack[-1][0] <= until:
            _, key, dur, nested = stack.pop()
            out[key] += max(dur - nested, 0)
            if stack:
                stack[-1][3] += dur

    for lo, hi, key in sorted(events, key=lambda e: (e[0], -e[1])):
        close(lo)
        stack.append([hi, key, hi - lo, 0])
    close(float("inf"))
    return out


def _host_annotations(data) -> dict[str, list[tuple[int, int]]]:
    spans: dict[str, list[tuple[int, int]]] = defaultdict(list)
    for plane in data.planes:
        if not plane.name.startswith(HOST_PLANE_PREFIX):
            continue
        for line in plane.lines:
            for ev, lo, hi in _events(line):
                name = ev.name
                if name.startswith(ANNOTATION_PREFIX):
                    spans[name].append((lo, hi))
    return spans


def reduce(path: str) -> dict:
    """-> per-device busy time, op table, categories, module events and
    attributed idle gaps of the trace at `path`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    annotations = _host_annotations(data)
    metadata = xplane_meta.op_metadata(path)
    devices = []
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        plane_meta = metadata.get(plane.name, {})
        ops: list[tuple[int, int, str]] = []
        keys: dict[str, tuple[str, str]] = {}   # text -> (name, opcode)
        op_meta: dict[str, dict] = {}           # name -> metadata stats
        modules: dict[str, list[int]] = defaultdict(list)
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev, lo, hi in _events(line):
                    text = ev.name
                    if text not in keys:
                        keys[text] = op_key(text)
                        op_meta[keys[text][0]] = plane_meta.get(text, {})
                    ops.append((lo, hi, keys[text][0]))
            elif line.name == MODULES_LINE:
                for ev, lo, hi in _events(line):
                    modules[ev.name.split("(")[0]].append(hi - lo)
        if ops:
            devices.append({"plane": plane.name, "ops": ops,
                            "opcode": dict(keys.values()),
                            "op_meta": op_meta, "modules": modules})
    if not devices:
        raise ValueError(f"no device plane with an {OPS_LINE!r} line in "
                         f"{path}: nothing ran on the device, or the "
                         f"trace layout changed")
    if annotations.get(WINDOW_ANNOTATION):
        lo, hi = annotations[WINDOW_ANNOTATION][0]
    else:
        lo = min(a for d in devices for a, _, _ in d["ops"])
        hi = max(b for d in devices for _, b, _ in d["ops"])
    window_ns = hi - lo
    host_spans = {k: _clip(_union(v), lo, hi)
                  for k, v in annotations.items()
                  if k != WINDOW_ANNOTATION}

    per_device = []
    for d in devices:
        inside = [(max(a, lo), min(b, hi), k) for a, b, k in d["ops"]
                  if b > lo and a < hi]
        busy = _union([(a, b) for a, b, _ in inside])
        op_ns = _self_times(inside)
        busy_ns = _length(busy)
        gaps = []
        edge = lo
        for a, b in busy + [(hi, hi)]:
            if a - edge >= MIN_GAP_NS:
                gaps.append((edge, a))
            edge = max(edge, b)
        opcode_ns: dict[str, int] = defaultdict(int)
        category_ns: dict[str, int] = defaultdict(int)
        source_ns: dict[str, int] = defaultdict(int)
        for name, ns in op_ns.items():
            meta = d["op_meta"][name]
            opcode_ns[d["opcode"][name]] += ns
            if meta.get("hlo_category"):
                category_ns[meta["hlo_category"]] += ns
            if meta.get("source"):
                source_ns[meta["source"]] += ns
        per_device.append({
            "plane": d["plane"], "busy_ns": busy_ns, "gaps": gaps,
            "idle_share": 1.0 - busy_ns / window_ns,
            "collective_ns": sum(opcode_ns.get(c, 0)
                                 for c in COLLECTIVE_OPCODES),
            "op_ns": {_label(n, d["op_meta"][n]): ns
                      for n, ns in op_ns.items()},
            "opcode_ns": dict(opcode_ns),
            # empty when the trace carries no such metadata
            "category_ns": dict(category_ns),
            "source_ns": dict(source_ns),
            # whole executions only count: the window cuts the first
            # and last, so the median stands for one execution
            "modules": {k: {"count": len(v), "total_ns": sum(v),
                            "median_ns": sorted(v)[len(v) // 2]}
                        for k, v in d["modules"].items()}})

    worst = max(per_device, key=lambda d: d["idle_share"])
    gap_by: dict[str, int] = defaultdict(int)
    for a, b in worst["gaps"]:
        best, best_ns = "unattributed", 0
        for name, spans in host_spans.items():
            ns = _length(_clip(spans, a, b))
            if ns > best_ns:
                best, best_ns = name, ns
        gap_by[best] += b - a
    ops_total: dict[str, int] = defaultdict(int)
    for d in per_device:
        for name, ns in d["op_ns"].items():
            ops_total[name] += ns
    n = len(per_device)
    return {
        "window_s": window_ns / 1e9,
        "devices": per_device,
        "busy_s_mean": sum(d["busy_ns"] for d in per_device) / n / 1e9,
        "idle_share_worst": worst["idle_share"],
        "worst_plane": worst["plane"],
        # seconds per op name, averaged over the chips, longest first
        "device_ops": [[name, ns / n / 1e9] for name, ns in sorted(
            ops_total.items(), key=lambda kv: -kv[1])],
        "idle_gaps": [[name, ns / 1e9] for name, ns in sorted(
            gap_by.items(), key=lambda kv: -kv[1])],
        "host_annotations": {k: {"count": len(v),
                                 "total_s": _length(v) / 1e9}
                             for k, v in host_spans.items()},
    }


def summary(tr: dict) -> dict:
    """The few numbers worth a progress line: first chip's shares of
    busy time by category and by program source line."""
    dev = tr["devices"][0]

    def shares(table: dict, top: int) -> list:
        return [[k, round(100.0 * ns / max(dev["busy_ns"], 1), 2)]
                for k, ns in sorted(table.items(),
                                    key=lambda kv: -kv[1])[:top]]

    return {"window_s": tr["window_s"], "busy_s_mean": tr["busy_s_mean"],
            "idle_share_worst": tr["idle_share_worst"],
            "top_ops": tr["device_ops"][:5],
            "idle_gaps": tr["idle_gaps"][:5],
            "modules": dev["modules"],
            "category_%": shares(dev["category_ns"], 8),
            "source_%": shares(dev["source_ns"], 12),
            "opcode_%": shares(dev["opcode_ns"], 8)}
