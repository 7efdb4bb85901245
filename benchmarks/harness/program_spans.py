"""The program's spans against the device's idle gaps, on one clock.

`obs.trace.SpanTracer.span` opens a `jax.profiler.TraceAnnotation`
named `apex.<span>` for the span's extent, so inside a profiler
session every program span is an event on a host-thread line of the
xplane's `/host:CPU` plane, in the same nanoseconds as the device
plane's ops. `trace_reduce.reduce` has already found the idle gaps of
the chip that idles most; this module lays the `apex.*` spans over
them:

- by span: for each span name, the idle seconds its intervals (the
  union over threads) overlap. Spans nest (`server.batch` holds
  `server.stack`, ...) and threads overlap, so the rows do not add up
  to the idle total; `uncovered` is the idle time under no span at all.
- `serve_host_share`: the share of the idle time overlapped by the
  serve thread's own host work, `server.stack` U `server.dispatch` U
  `server.scatter` — the part of a batch in which the chip waits for
  Python on the thread that feeds it (not `server.collect`, a wait for
  requests, nor `server.fetch`, in which the device runs).

A trace with no `apex.*` event (obs off, or a program from before the
spans went onto the profiler's clock) gives None.
"""

from __future__ import annotations

from collections import defaultdict

from benchmarks.harness.trace_reduce import (
    HOST_PLANE_PREFIX, _clip, _events, _length, _union)

SPAN_PREFIX = "apex."
SERVE_HOST_SPANS = ("server.stack", "server.dispatch", "server.scatter")


def host_spans(path: str) -> dict[str, list[tuple[int, int]]]:
    """span name (prefix dropped) -> its (start, end) ns on every host
    line of the trace at `path`."""
    from jax.profiler import ProfileData

    spans: dict[str, list[tuple[int, int]]] = defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(HOST_PLANE_PREFIX):
            continue
        for line in plane.lines:
            for ev, lo, hi in _events(line):
                if ev.name.startswith(SPAN_PREFIX):
                    spans[ev.name[len(SPAN_PREFIX):]].append((lo, hi))
    return dict(spans)


def _intersect(a: list[tuple[int, int]],
               b: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_by_span(trace: dict, path: str) -> dict | None:
    """`trace`: what `trace_reduce.reduce(path)` returned. -> idle
    seconds of the worst chip by `apex.*` span (longest first), the
    idle seconds under no span, and the serve thread's host share."""
    spans = host_spans(path)
    if not spans:
        return None
    worst = next(d for d in trace["devices"]
                 if d["plane"] == trace["worst_plane"])
    gaps = [tuple(g) for g in worst["gaps"]]
    idle_ns = _length(gaps)
    if not gaps or idle_ns <= 0:
        return None
    lo, hi = gaps[0][0], gaps[-1][1]
    merged = {name: _clip(_union(iv), lo, hi)
              for name, iv in spans.items()}
    by_span = sorted(
        ([name, _length(_intersect(gaps, iv)) / 1e9, len(spans[name])]
         for name, iv in merged.items()), key=lambda row: -row[1])
    covered = _union([x for iv in merged.values() for x in iv])
    serve_ns = _length(_intersect(gaps, _union(
        [x for name in SERVE_HOST_SPANS for x in merged.get(name, ())])))
    return {
        "plane": worst["plane"],
        "idle_s": idle_ns / 1e9,
        # [span, idle seconds it overlaps, events in the trace]
        "by_span": by_span,
        "uncovered_s": (idle_ns - _length(_intersect(gaps, covered)))
        / 1e9,
        "serve_host_s": serve_ns / 1e9,
        "serve_host_share": serve_ns / idle_ns,
    }


def of_facts(facts: dict) -> dict | None:
    """The table of a traced run's newest xplane."""
    path = facts["runtime"].newest_xplane()
    if not path or not facts.get("trace"):
        return None
    return idle_by_span(facts["trace"], path)
