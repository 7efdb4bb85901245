"""Host-side span tracing in Chrome/Perfetto `trace_event` format.

The runtime's stage structure (actor inference+env step, replay
add/sample, learner SGD, priority write-back, target sync, checkpoint
I/O, inference-server batch assembly) is invisible to `jax.profiler`:
the XLA trace shows device ops, not which HOST loop was waiting on
which dispatch. This tracer records wall-clock spans from the Python
side into the `trace_event` JSON that chrome://tracing and
https://ui.perfetto.dev load directly — one timeline row per thread,
so the actor/ingest/learner overlap (or lack of it) is readable at a
glance.

Design constraints:
- Low overhead: a span costs two `perf_counter` calls, one
  `jax.profiler.TraceAnnotation` (a flag test outside a profiler
  session) and one lock-guarded append of plain values; nothing is
  formatted or written until `close()`. A bounded buffer (`max_events`) caps memory
  on long runs — once full, new events are counted as dropped, never
  resized.
- One clock with the device: inside a `jax.profiler` session every
  span also lands on the host plane of the xplane as `apex.<name>`, so
  a device idle gap can be laid against the host span that covers it.
  Aggregates and the JSON file keep the bare name.
- Cross-thread intervals: `record(name, t0, t1)` folds an interval
  that starts on one thread and ends on another (a request's queue
  wait) into the aggregates and the JSON; it has no single thread to
  annotate, so it carries no profiler annotation.
- Fused stages: stages that execute INSIDE one XLA dispatch (the
  draw, the loss, the optimizer, the target sync, the health norms and
  the priority write-back all live inside the train jit) cannot be
  timed from the host and get no event here. They show on the DEVICE
  plane of a `jax.profiler` trace: every op carries the
  `jax.named_scope` of its stage (`cycle.sample` ... `cycle.write_back`,
  runtime/learner.py::CYCLE_SCOPES), under the host's
  `apex.learner.train` span on the same clock. `mark()` is for a host
  event with no duration of its own (`actor.ship`).
- Stage aggregates: every span also folds into a per-name
  (count, total_s, max_s) table so the JSONL stream can carry a
  stage-time breakdown (obs/report.py) without parsing the trace file.

The no-op twin `NullTracer` keeps every call site branch-free when
tracing is off (ObsConfig.trace_path empty / obs disabled): `span`
hands back the one preallocated `NULL_SPAN`, so a disabled span site
allocates nothing.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any

ANNOTATION_PREFIX = "apex."


class _NullSpan:
    """The context manager every disabled span site shares."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> bool:
        return False


NULL_SPAN = _NullSpan()


class NullTracer:
    """API-compatible no-op tracer (shared singleton `NULL_TRACER`)."""

    enabled = False

    def span(self, name: str, **args: Any) -> _NullSpan:
        return NULL_SPAN

    def record(self, name: str, t0: float, t1: float,
               **args: Any) -> None:
        pass

    def mark(self, name: str, **args: Any) -> None:
        pass

    def remote_span(self, name: str, dur_s: float, age_s: float = 0.0,
                    peer: str = "", **args: Any) -> None:
        pass

    def aggregates(self) -> dict[str, dict[str, float]]:
        return {}

    def close(self) -> None:
        pass


NULL_TRACER = NullTracer()


class _Span:
    """One open span: the profiler annotation and the host stamps."""

    __slots__ = ("_tracer", "_name", "_args", "_annotation", "_t0")

    def __init__(self, tracer: "SpanTracer", name: str, args: dict):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._annotation = tracer._annotate(ANNOTATION_PREFIX + name)

    def __enter__(self) -> None:
        self._annotation.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self._annotation.__exit__(*exc)
        self._tracer._record(self._name, self._t0, t1, self._args)
        return False


class SpanTracer:
    """Thread-safe span recorder writing one `trace_event` JSON file.

    Events use the 'X' (complete) phase with microsecond ts/dur
    relative to tracer construction; pid/tid map to the OS process and
    Python thread ids, with 'M' metadata events naming each thread so
    Perfetto's track labels read "learner", "actor-3", ... instead of
    raw ids.
    """

    enabled = True

    def __init__(self, path: str, max_events: int = 200_000):
        self._path = path
        self._max = max_events
        self._lock = threading.Lock()
        # events as five parallel columns of plain values (args dicts
        # of plain values are not GC-tracked either): a retained tuple
        # or dict per event is a GC-tracked survivor, and thousands a
        # second of those bring on full collections — 130 ms pauses
        # and 4% of pong_live's throughput on the v5e host (PERF.md)
        self._ev_name: list[str] = []
        self._ev_t0: list[float] = []
        self._ev_dur: list[float] = []
        self._ev_tid: list[int] = []
        self._ev_args: list[dict | None] = []
        self._dropped = 0
        self._thread_names: dict[int, str] = {}
        self._peer_tids: dict[str, int] = {}  # synthetic remote tracks
        self._agg: dict[str, list[float]] = {}  # name -> [count, total, max]
        self._t0 = time.perf_counter()
        self._pid = os.getpid()
        self._closed = False
        # jax only when a tracer is built: this module stays importable
        # by processes that never touch a backend
        import jax.profiler

        self._annotate = jax.profiler.TraceAnnotation

    def span(self, name: str, **args: Any) -> _Span:
        return _Span(self, name, args)

    def record(self, name: str, t0: float, t1: float,
               **args: Any) -> None:
        """Fold an interval measured by the caller (`perf_counter`
        stamps), e.g. one that starts on the thread that enqueues a
        request and ends on the thread that collects it."""
        self._record(name, t0, t1, args)

    def mark(self, name: str, **args: Any) -> None:
        """Instant-ish event: something that happened on this thread and
        has no duration of its own (1us nominal so 'X' renderers still
        draw it)."""
        t = time.perf_counter()
        self._record(name, t, t + 1e-6, args, mark=True)

    def remote_span(self, name: str, dur_s: float, age_s: float = 0.0,
                    peer: str = "", **args: Any) -> None:
        """Record a span REPORTED by a remote peer over the telemetry
        wire. The peer's clock domain does not cross the wire; only the
        event's AGE does — the event lands at local-now minus age_s on
        a synthetic `peer/<id>` track. That keeps cross-process
        correlation honest: ordering within a track and the shared
        correlation args (batch_id) are exact, absolute alignment
        across tracks is age-accurate only."""
        now = time.perf_counter()
        t1 = now - max(float(age_s), 0.0)
        t0 = t1 - max(float(dur_s), 0.0)
        label = f"peer/{peer or '?'}"
        with self._lock:
            tid = self._peer_tids.get(label)
            if tid is None:
                # high base keeps synthetic tids clear of OS thread ids
                tid = self._peer_tids[label] = 1 << 40 | len(self._peer_tids)
                self._thread_names[tid] = label
        self._record(name, t0, t1, dict(args, peer=peer), tid=tid)

    def _record(self, name: str, t0: float, t1: float, args: dict,
                mark: bool = False, tid: int | None = None) -> None:
        local = tid is None
        if local:
            tid = threading.get_ident()
        dur = t1 - t0
        with self._lock:
            if local and tid not in self._thread_names:
                self._thread_names[tid] = threading.current_thread().name
            a = self._agg.get(name)
            if a is None:
                a = self._agg[name] = [0, 0.0, 0.0]
            a[0] += 1
            if not mark:  # marks carry no host-measurable duration
                a[1] += dur
                a[2] = max(a[2], dur)
            if len(self._ev_name) >= self._max:
                self._dropped += 1
                return
            # the trace_event dicts are built in close(), off every
            # hot path
            self._ev_name.append(name)
            self._ev_t0.append(t0)
            self._ev_dur.append(dur)
            self._ev_tid.append(tid)
            self._ev_args.append(args or None)

    def aggregates(self) -> dict[str, dict[str, float]]:
        """Per-span-name stage totals (counts every event, including
        ones dropped from the bounded trace buffer)."""
        with self._lock:
            return {name: {"count": int(c), "total_s": t, "max_s": mx}
                    for name, (c, t, mx) in sorted(self._agg.items())}

    def close(self) -> None:
        """Write the trace file (valid JSON even with zero events)."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            columns = (self._ev_name, self._ev_t0, self._ev_dur,
                       self._ev_tid, self._ev_args)
            self._ev_name, self._ev_t0, self._ev_dur = [], [], []
            self._ev_tid, self._ev_args = [], []
            meta = [{"name": "thread_name", "ph": "M", "pid": self._pid,
                     "tid": tid, "args": {"name": tname}}
                    for tid, tname in sorted(self._thread_names.items())]
            dropped = self._dropped
        events = []
        for name, t0, dur, tid, args in zip(*columns):
            ev = {"name": name, "cat": "apex", "ph": "X",
                  "ts": (t0 - self._t0) * 1e6, "dur": dur * 1e6,
                  "pid": self._pid, "tid": tid}
            if args:
                ev["args"] = args
            events.append(ev)
        payload = {"traceEvents": meta + events,
                   "displayTimeUnit": "ms",
                   "otherData": {"dropped_events": dropped}}
        os.makedirs(os.path.dirname(self._path) or ".", exist_ok=True)
        tmp = self._path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(payload, fh)
        os.replace(tmp, self._path)


def load_trace(path: str) -> dict:
    """Load a trace file back (tests / report CLI)."""
    with open(path) as fh:
        return json.load(fh)


def span_names(trace: dict) -> set[str]:
    """Distinct span ('X' event) names in a loaded trace."""
    return {ev["name"] for ev in trace.get("traceEvents", ())
            if ev.get("ph") == "X"}
