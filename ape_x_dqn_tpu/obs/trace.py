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
- Low overhead: a span costs three `perf_counter` calls, one
  `jax.profiler.TraceAnnotation` (a flag test outside a profiler
  session), one lock-guarded append of plain values and, once in
  `CPU_EVERY_S` seconds a name, two `thread_time` calls; nothing is
  formatted or written until `close()`. A bounded buffer
  (`max_events`) caps memory on long runs — once full, new events are
  counted as dropped, never resized.
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
- Two clocks (ISSUE 52): a span opens and closes on one thread, so it
  can stamp `time.thread_time()` around its wall stamps — outside them,
  so the wall extent is what it is without the second clock — and the
  CPU seconds go to `args.cpu_us` on the JSON event and to a row of
  their own, `<name>.cpu` (count = the spans that stamped, total_s =
  their CPU seconds, max_s = the largest single one). Wall minus CPU
  (of the means) is time the thread had the span open and was on no
  core: the GIL, a lock, a transfer the runtime waits for. **The CPU
  clock is sampled in time**: a span stamps it when its name's last
  stamp is `CPU_EVERY_S` old, so a rare span always does and a hot one
  costs a bounded number of reads a second. Unlike `perf_counter` that
  clock is a system call on every Linux (no vDSO): 0.4 us on a plain
  host, 6-10 us with the GIL held under gVisor, which the TPU machines
  run — stamping every span there cost the serve loop 8% of its rate
  (PERF.md section 6, PR 52). gVisor also ticks that clock in steps of
  10 ms, so a single span's reading is 0 or 10 ms there and a `.cpu`
  row's mean over some hundreds of spans is good to about 15%: a
  diagnostic. What is exact to a tick is the clock itself: every stamp
  is also the thread's CPU seconds so far, and `aggregates()` hands the
  latest of each thread out as `thread.<role>.cpu` (count 0; the role
  is the thread's name less a trailing number, so `actor-3` and
  `actor-4` add up under `actor`), as it hands `time.process_time()`
  out as `process.cpu`: two snapshots bracket the CPU seconds of a
  thread that keeps opening spans to within one `CPU_EVERY_S` of its
  work (a name keeps one cadence for all the threads that open it, so
  a role of N threads with the same spans, as actors are, is staler N
  times), and of the whole process — every Python thread and the
  runtime's own — exactly. A `record()`ed interval crosses threads and
  carries no CPU, and a mark never does; `lap()` is for the interval
  one thread measures end to end without a `with` (the serve loop's
  period).
- The collector's pauses: a live tracer keeps one `gc.callbacks` hook
  that makes every collection a span `host.gc` (`generation=`) on the
  thread the collector ran on, annotation included. `close()` takes
  the hook out; a `NullTracer` never installs one.

The no-op twin `NullTracer` keeps every call site branch-free when
tracing is off (ObsConfig.trace_path empty / obs disabled): `span`
hands back the one preallocated `NULL_SPAN`, so a disabled span site
allocates nothing.
"""

from __future__ import annotations

import gc
import json
import os
import threading
import time
import weakref
from typing import Any

ANNOTATION_PREFIX = "apex."
CPU_SUFFIX = ".cpu"  # a row of CPU seconds: a span's, a thread's, the process's
PROCESS_CPU = "process.cpu"
THREAD_PREFIX = "thread."
GC_SPAN = "host.gc"
# a name reads its thread's CPU clock when its last reading is this old:
# twenty reads a second a hot name, every span of a rare one. Measured,
# not chosen for a workload: at 6-10 us a read under gVisor that is
# under 0.1% of a thread, and every span of `pong_live` reading it cost
# 8% of the traced rate (PERF.md section 6, PR 52)
CPU_EVERY_S = 0.05


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

    def lap(self, name: str, since: tuple | None = None,
            **args: Any) -> None:
        return None

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
    """One open span: the profiler annotation and the host stamps,
    the thread's CPU clock outside the wall clock's."""

    __slots__ = ("_tracer", "_name", "_args", "_annotation", "_t0",
                 "_c0")

    def __init__(self, tracer: "SpanTracer", name: str, args: dict):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._annotation = tracer._annotate(ANNOTATION_PREFIX + name)

    def __enter__(self) -> None:
        self._c0 = self._tracer._cpu_clock(self._name,
                                           time.perf_counter())
        self._annotation.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self._annotation.__exit__(*exc)
        c0 = self._c0
        self._tracer._record(
            self._name, self._t0, t1, self._args,
            cpu=None if c0 is None else (c0, time.thread_time()))
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
        # name -> `perf_counter` of its last CPU stamp. Written without
        # the lock: two threads that share a name can both stamp, which
        # costs one read more and nothing else
        self._cpu_stamped: dict[str, float] = {}
        # thread id -> its CPU clock at its latest closing stamp
        self._thread_cpu: dict[int, float] = {}
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
        self._ev_cpu: list[float | None] = []
        self._dropped = 0
        self._thread_names: dict[int, str] = {}
        self._peer_tids: dict[str, int] = {}  # synthetic remote tracks
        # name -> [count, total, max, cpu count, cpu total, cpu max]
        self._agg: dict[str, list[float]] = {}
        self._t0 = time.perf_counter()
        self._pid = os.getpid()
        self._closed = False
        # jax only when a tracer is built: this module stays importable
        # by processes that never touch a backend
        import jax.profiler

        self._annotate = jax.profiler.TraceAnnotation
        # the collector's spans keep storage of their own, which only
        # the hook writes: a collection starts between any two
        # bytecodes, also on a thread that is inside `_record` and
        # holds `_lock`, so the hook takes no lock. Collections do not
        # nest and the hook runs under the GIL, so one call writes at
        # a time; the row is swapped whole, never edited, and the
        # columns join the others in `close()`
        self._gc_open: tuple | None = None  # (annotation, t0, c0)
        self._gc_row: tuple = (0, 0.0, 0.0, 0, 0.0, 0.0)
        self._gc_t0: list[float] = []
        self._gc_dur: list[float] = []
        self._gc_cpu: list[float] = []
        self._gc_tid: list[int] = []
        self._gc_gen: list[int] = []
        self._gc_dropped = 0
        self._gc_threads: dict[int, str] = {}
        # through a weak reference: a tracer that is dropped unclosed
        # takes its hook with it instead of living on in gc.callbacks
        ref = weakref.ref(self)

        def hook(phase: str, info: dict) -> None:
            tracer = ref()
            if tracer is not None:
                tracer._on_gc(phase, info)

        self._gc_hook = hook
        gc.callbacks.append(hook)
        weakref.finalize(self, _unhook, hook)

    def span(self, name: str, **args: Any) -> _Span:
        return _Span(self, name, args)

    def record(self, name: str, t0: float, t1: float,
               **args: Any) -> None:
        """Fold an interval measured by the caller (`perf_counter`
        stamps), e.g. one that starts on the thread that enqueues a
        request and ends on the thread that collects it. Such an
        interval has no thread to charge and carries no CPU."""
        self._record(name, t0, t1, args)

    def lap(self, name: str, since: tuple | None = None,
            **args: Any) -> tuple:
        """Close the interval `since` opened, if any, as a `name` on
        both clocks, and open the next one where it ends: for a loop
        whose iterations ARE the spans, on one thread, with args known
        only at the end (`server.period`). It carries no profiler
        annotation: a lap covers the spans opened inside it, and the
        idle-gap account takes the innermost."""
        p1 = time.perf_counter()
        if since is not None:
            p0, c0 = since
            self._record(
                name, p0, p1, args,
                cpu=None if c0 is None else (c0, time.thread_time()))
        return p1, self._cpu_clock(name, p1)

    def mark(self, name: str, **args: Any) -> None:
        """Instant-ish event: something that happened on this thread and
        has no duration of its own (1us nominal so 'X' renderers still
        draw it)."""
        t = time.perf_counter()
        self._record(name, t, t + 1e-6, args, mark=True)

    def _cpu_clock(self, name: str, now: float) -> float | None:
        """This thread's CPU clock if `name` last read it `CPU_EVERY_S`
        before `now` or never; nothing otherwise."""
        if now - self._cpu_stamped.get(name, -1e18) < CPU_EVERY_S:
            return None
        self._cpu_stamped[name] = now
        return time.thread_time()

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
                mark: bool = False, tid: int | None = None,
                cpu: tuple[float, float] | None = None) -> None:
        """`cpu`: this thread's CPU clock at the interval's two ends."""
        local = tid is None
        if local:
            tid = threading.get_ident()
        dur = t1 - t0
        used = None
        with self._lock:
            if local and tid not in self._thread_names:
                self._thread_names[tid] = threading.current_thread().name
            a = self._agg.get(name)
            if a is None:
                a = self._agg[name] = [0, 0.0, 0.0, 0, 0.0, 0.0]
            a[0] += 1
            if not mark:  # marks carry no host-measurable duration
                a[1] += dur
                a[2] = max(a[2], dur)
            if cpu is not None:
                used = cpu[1] - cpu[0]
                a[3] += 1
                a[4] += used
                a[5] = max(a[5], used)
                self._thread_cpu[tid] = cpu[1]
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
            self._ev_cpu.append(used)

    def _on_gc(self, phase: str, info: dict) -> None:
        """The `gc.callbacks` hook: one collection, `"start"` to
        `"stop"` on the thread it ran on, as the span `host.gc`."""
        if phase == "start":
            c0 = time.thread_time()
            annotation = self._annotate(ANNOTATION_PREFIX + GC_SPAN)
            annotation.__enter__()
            self._gc_open = (annotation, time.perf_counter(), c0)
            return
        if self._gc_open is None:  # hooked between a start and its stop
            return
        annotation, t0, c0 = self._gc_open
        dur = time.perf_counter() - t0
        self._gc_open = None
        annotation.__exit__(None, None, None)
        cpu = time.thread_time() - c0
        n, total, mx, _, cpu_total, cpu_mx = self._gc_row
        self._gc_row = (n + 1, total + dur, max(mx, dur), n + 1,
                        cpu_total + cpu, max(cpu_mx, cpu))
        if len(self._gc_t0) >= self._max:
            self._gc_dropped += 1
            return
        tid = threading.get_ident()
        if tid not in self._gc_threads:
            self._gc_threads[tid] = threading.current_thread().name
        self._gc_t0.append(t0)
        self._gc_dur.append(dur)
        self._gc_cpu.append(cpu)
        self._gc_tid.append(tid)
        self._gc_gen.append(info["generation"])

    def aggregates(self) -> dict[str, dict[str, float]]:
        """Per-span-name stage totals (counts every event, including
        ones dropped from the bounded trace buffer). A name that
        stamped CPU has the row `<name>.cpu` too; `process.cpu` and
        `thread.<role>.cpu` (count 0) are the process's CPU clock at
        this call and each stamping thread's at its latest stamp."""
        with self._lock:
            rows = {name: tuple(a) for name, a in self._agg.items()}
            threads = [(self._thread_names[tid], c)
                       for tid, c in self._thread_cpu.items()]
        if self._gc_row[0]:
            rows[GC_SPAN] = self._gc_row
        out = {PROCESS_CPU: {"count": 0, "total_s": time.process_time(),
                             "max_s": 0.0}}
        for name, c in threads:
            role = name.rstrip("0123456789").rstrip("-_") or name
            row = out.setdefault(THREAD_PREFIX + role + CPU_SUFFIX,
                                 {"count": 0, "total_s": 0.0, "max_s": 0.0})
            row["total_s"] += c
        for name, (c, t, mx, cpu_c, cpu_t, cpu_mx) in rows.items():
            out[name] = {"count": int(c), "total_s": t, "max_s": mx}
            if cpu_c:
                out[name + CPU_SUFFIX] = {"count": int(cpu_c),
                                          "total_s": cpu_t,
                                          "max_s": cpu_mx}
        return dict(sorted(out.items()))

    def close(self) -> None:
        """Write the trace file (valid JSON even with zero events) and
        take the collector's hook out."""
        _unhook(self._gc_hook)
        cut = self._gc_open
        if cut is not None:  # closed inside a collection: no stop comes
            self._gc_open = None
            cut[0].__exit__(None, None, None)
        with self._lock:
            if self._closed:
                return
            self._closed = True
            columns = (self._ev_name, self._ev_t0, self._ev_dur,
                       self._ev_tid, self._ev_args, self._ev_cpu)
            self._ev_name, self._ev_t0, self._ev_dur = [], [], []
            self._ev_tid, self._ev_args, self._ev_cpu = [], [], []
            names = {**self._gc_threads, **self._thread_names}
            meta = [{"name": "thread_name", "ph": "M", "pid": self._pid,
                     "tid": tid, "args": {"name": tname}}
                    for tid, tname in sorted(names.items())]
            dropped = self._dropped + self._gc_dropped
        collections = len(self._gc_t0)
        columns = tuple(
            col + gc_col for col, gc_col in zip(columns, (
                [GC_SPAN] * collections, self._gc_t0, self._gc_dur,
                self._gc_tid,
                [{"generation": g} for g in self._gc_gen],
                self._gc_cpu)))
        events = []
        for name, t0, dur, tid, args, cpu in zip(*columns):
            ev = {"name": name, "cat": "apex", "ph": "X",
                  "ts": (t0 - self._t0) * 1e6, "dur": dur * 1e6,
                  "pid": self._pid, "tid": tid}
            if cpu is not None:
                args = {**(args or {}), "cpu_us": cpu * 1e6}
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


def _unhook(hook) -> None:
    try:
        gc.callbacks.remove(hook)
    except ValueError:  # taken out already
        pass


def load_trace(path: str) -> dict:
    """Load a trace file back (tests / report CLI)."""
    with open(path) as fh:
        return json.load(fh)


def span_names(trace: dict) -> set[str]:
    """Distinct span ('X' event) names in a loaded trace."""
    return {ev["name"] for ev in trace.get("traceEvents", ())
            if ev.get("ph") == "X"}
