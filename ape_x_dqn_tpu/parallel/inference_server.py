"""Batched TPU inference server for actor policy evaluation.

The reference evaluates policies per-actor on GPUs (SURVEY.md §2.3 item
4); here many actors RPC observations to one server thread that pads
them into fixed-size buckets and runs a single jitted forward on the
TPU, then scatters results back (BASELINE.json north_star: "actor policy
evaluation is batched onto a TPU inference server").

Dynamic batching: the server collects requests until `max_batch` are
waiting or the oldest has waited `deadline_ms` (latency/throughput
trade-off, SURVEY.md §7 hard part 3). Batches are padded to the next
power of two so XLA compiles a handful of bucket shapes once.

The serve loop (ISSUE 40) keeps ONE forward in flight — a two-deep
software pipeline inside the one serve thread:

    collect -> stack -> dispatch batch k+1   (the jit call returns at
                                              once; the outputs' copy to
                                              the host is started behind it)
            -> fetch -> scatter batch k      (its result has had a whole
                                              host period to arrive)

so the device round trip (host -> device copy, launch, device -> host
copy, the wake-up and the wait to win the GIL back from the clients the
last scatter released) runs beside the next batch's host work instead
of being a part of every period. The rule that keeps a lone actor's
latency what it was: **with a batch in flight the serve thread never
waits on the queue** — neither for a first request nor for the fill
deadline. Batch k+1 goes ahead of k's fetch only when its requests are
ALREADY waiting (`_collect(block=False)`: the held deque, then
`get_nowait`); with nothing waiting k is fetched and scattered at once,
and only then does the loop collect with the deadline as ever. It
adapts on the one thing the server can see, its own queue: a saturated
fleet sees the pipeline, `query()`, warm-up traffic and a lone actor see
the serial loop. At most one batch is ahead. Replies leave in arrival
order, k before k+1; each batch keeps the params and version it read at
its own dispatch; an error at dispatch or at fetch reaches exactly the
requests of the batch it belongs to; `stop()` replies to a batch in
flight. Spans: `server.stack` / `.dispatch` / `.fetch` / `.scatter`
still tile the thread's time and carry `batch=seq`; `server.batch`
(stack start -> scatter end) overlaps its successor's first half, so it
is a `record()`ed interval with no profiler annotation; `server.ahead`,
once per batch dispatched with its predecessor unfetched, runs from
that dispatch to the start of the predecessor's fetch, and
count(`server.ahead`) / count(`server.batch`) is the share of batches
the pipeline engaged for. `server.fetch` is now what is LEFT of the
round trip after a successor's stack + dispatch. `server.period`
(ISSUE 52) is the thread's whole time a batch answered — one loop
iteration with a forward in flight, the dispatching and the replying
iteration together otherwise — with `server.collect` and the four spans
as its children; what it holds beyond them is the loop's own remainder
(the stats lock, `on_server_batch`, the records themselves).

`MultiPolicyInferenceServer` keeps a loop of its own (`_dispatch_loop`,
strictly serial) and is not pipelined: its admission classes, shedding
and per-request deadlines make "what is already waiting" a different
question (a forward dispatched ahead has admitted requests a later,
more urgent class could no longer overtake), and no benchmark cell runs
it, so a before and after could not be measured.

The slot path (ISSUE 55): a net whose per-session state cannot ride a
query (models/minicpm_sala_q.py: megabytes of float32 matrices and two
KiB a position) is served with that state ON THE DEVICE BETWEEN
QUERIES. `BatchedInferenceServer(..., slots=Slots(...))` makes the
choice once, at construction, by binding `_collect`, `_dispatch` and
`_reply` to their slot forms; without `slots` every method, span and
jitted program is what it was. The program is `apply_fn(params, state,
inputs) -> (outputs, state)`: `state` is DONATED through every dispatch
and never copied whole, and dispatch order is state order, so the one
batch in flight needs no lock - a closed-loop caller has no second
request for a slot until the first is answered, and a request for a
slot that the batch being stacked already holds waits for the next
batch. A query names its sessions (`slot` [rows]) and says which begin
(`fresh`); the host's ledger (parallel/slot_pool.py) hands a beginning
session one contiguous range of the shared pool for the length it
declares (`max_len`, optional) and the dispatch carries each row's
`base`. A session that does not fit fails its query with `SlotPoolFull`
and leaves the rest of the batch alone. A dispatch that fails fails its
own requests and puts the ledger back to what the device's lengths
agree with; one that took the donated state with it leaves a zeroed
state and every session that was live lost by name (`SlotStateLost`,
`_lose_slot_state`), and the server serves on. Two kinds of request, told
apart by `obs`: [rows] one token a session (a decode step) and [rows,
prefill_chunk] with `n_valid` [rows] (a prefill chunk); a dispatch
holds rows of ONE kind, rows pad to a power of two and no further than
the kind's budget (`max_batch`, `prefill_rows`; a padding row is the
scratch slot), and `warmup()` runs every bucket of both kinds once on
scratch rows. With a reply owed that the device has not finished, the
serve thread spends the wait on its queue (`_collect_slots`): what
arrives while a step runs is dispatched behind it. The reply is `outputs` minus `counters` (summed into
`slot_counters`) and, unless some request of the batch said
`want_sel`, minus `sel` (which only a net that selects answers:
models/jamba_q.py has none). Spans `server.stack` / `server.dispatch` carry
`n=` and `rows=` there; gauges `server.slots_live`,
`server.slot_blocks_held`; marks `server.slot_admit` /
`server.slot_free`.

Generic over the request pytree: a request is (inputs_pytree,) and the
reply is outputs_pytree — plain Q-nets send obs and get Q-values;
recurrent nets send (obs, (c, h)) and get (q, (c', h')).

Mesh-sharded mode: pass `mesh` to shard each batch's leading axis across
every device of a `jax.sharding.Mesh` with the params replicated, so
forwards/s scales with chip count (SURVEY.md §5 "weight broadcast →
all-gather over ICI to inference-server shards"). Buckets round up to a
multiple of the mesh size so every shard gets identical work; the dist
learner's `publish_params` already hands over mesh-replicated buffers,
so a publication is exactly the ICI all-gather the survey names.

Multi-tenant serving tier (ISSUE 13): `MultiPolicyInferenceServer`
serves MANY policies from one chip behind a single continuous-batching
admission queue. Requests are tagged (policy_id, priority class);
an admission thread moves them into per-family priority deques while
the dispatch thread is forwarding — admission never waits on a
collect-then-serve round. Same-family tenants coalesce into one
stacked/gather-indexed forward (`vmap` over per-example params rows),
so 57 heads cost one dispatch, not 57. The admission controller sheds
load from the lowest priority class first when queue depth crosses the
SLO line (class 0 is never shed), expires requests past their deadline
with errors attributed to the policy_id, and raises/clears a
backpressure signal the transport layer can act on. Drivers talk to
the tier through `register_policy`'s TenantClient, which keeps the
exact BatchedInferenceServer client surface.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ape_x_dqn_tpu.obs.core import NULL_OBS
from ape_x_dqn_tpu.obs.health import make_lock
from ape_x_dqn_tpu.obs.trace import ANNOTATION_PREFIX
from ape_x_dqn_tpu.parallel.slot_pool import SlotPool
from ape_x_dqn_tpu.utils.misc import next_pow2


class _Request:
    __slots__ = ("inputs", "n", "event", "result", "t_enq")

    def __init__(self, inputs: Any, n: int = 0):
        """n == 0: single item, no batch dim on any leaf.
        n >= 1: a multi-item request whose leaves carry a leading [n]
        batch dim (vector actors ship one request per vector step)."""
        self.inputs = inputs
        self.n = n
        self.event = threading.Event()
        self.result: Any = None
        self.t_enq = time.perf_counter()  # serving-SLO latency anchor

    @property
    def items(self) -> int:
        return self.n if self.n else 1


@dataclass(slots=True)
class _Flight:
    """A batch past its dispatch: what its reply still needs."""

    reqs: list[_Request]
    n: int              # items
    padded: int
    seq: int
    version: int        # of the params it was dispatched with
    out: Any            # device outputs, their copy to the host started
    t0: float           # server.batch's start (stamped only when traced)
    t_dispatch: float   # server.dispatch's start (likewise)
    counters: Any = None  # slot path: the program's counters, on the device


@dataclass
class Slots:
    """What makes a BatchedInferenceServer a slot server: the host's
    ledger, the device state the program threads (replaced at every
    dispatch), and the second bucket kind's geometry."""

    pool: SlotPool
    state: Any
    prefill_chunk: int
    prefill_rows: int


class BatchedInferenceServer:
    def __init__(self, apply_fn: Callable, params: Any,
                 max_batch: int = 64, deadline_ms: float = 2.0,
                 mesh: Mesh | None = None, obs: Any = None,
                 slots: Slots | None = None):
        """apply_fn(params, batched_inputs_pytree) -> batched outputs.

        slots: optional — serve a net whose state lives in slots on the
        device: apply_fn(params, state, inputs) -> (outputs, state); see
        module docstring, "The slot path".

        mesh: optional — shard every batch's leading axis over all mesh
        devices (params replicated); see module docstring.
        obs: optional obs.core.Obs facade — per-batch span + batch-fill
        / param-lag / queue-depth instruments and the server heartbeat
        (NULL_OBS when omitted, so the hot loop stays branch-free).
        """
        self._slots = slots
        if slots is not None:
            if mesh is not None:
                raise NotImplementedError(
                    "a slot server runs on one device: its state is not "
                    "sharded over a mesh")
            self._apply = jax.jit(apply_fn, donate_argnums=(1,))
            self._batched_sharding = None
            self._min_bucket = 1
            # the choice of path, made once
            self._collect = self._collect_slots
            self._dispatch = self._dispatch_slots
            self._reply = self._reply_slots
            self.slot_counters: dict[str, int] = {}  # serve thread writes
            self._owed: Any = None  # the newest dispatch's `q`, on the device
            # what a zeroed state is made from, should a failed dispatch
            # take the donated one with it (`_lose_slot_state`)
            self._state_spec = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                slots.state)
        elif mesh is not None:
            # One sharding as a pytree prefix: dim 0 of every input and
            # output leaf is split over the flattened (dp, tp) device
            # grid; params replicate. Numpy inputs commit to these
            # shardings at dispatch, replies gather back host-side.
            batched = NamedSharding(mesh, P(tuple(mesh.axis_names)))
            self._apply = jax.jit(
                apply_fn,
                in_shardings=(NamedSharding(mesh, P()), batched),
                out_shardings=batched)
            # explicit placement before dispatch: under a multi-process
            # runtime, jit rejects numpy args with non-trivial shardings
            # (it cannot tell process-local from global data); device_put
            # onto the (all-addressable) local mesh is unambiguous
            self._batched_sharding = batched
            self._min_bucket = int(mesh.size)
        else:
            self._apply = jax.jit(apply_fn)
            self._batched_sharding = None
            self._min_bucket = 1
        self._params = params  # guarded-by: _lock
        self._params_version = 0  # guarded-by: _lock
        self._max_batch = max_batch
        self._deadline_s = deadline_ms / 1000.0
        self._q: queue.Queue[_Request] = queue.Queue()
        # popped-but-not-admitted requests (would overflow max_batch)
        # held in arrival order for later batches — only the serve
        # thread touches it
        self._held: deque[_Request] = deque()
        # bucket sizes already AOT-compiled: warmup() is re-entrant
        # across update_params epochs without re-paying compiles —
        # only the caller's thread touches it (warmup is pre-traffic)
        self._warm_buckets: set[int] = set()
        self._stop = threading.Event()
        # _lock guards the published params (swapped by the driver's
        # ingest thread, read by the serve thread) and the served-stat
        # counters (bumped by the serve thread, read by stats callers)
        self._lock = make_lock("inference_server._lock")
        self._batches_served = 0  # guarded-by: _lock
        self._items_served = 0  # guarded-by: _lock
        self._obs = obs if obs is not None else NULL_OBS
        self._obs.register("inference-server")
        # spans exist only under a live tracer; the per-request stamps
        # of server.queue_wait are skipped outright without one
        self._traced = bool(self._obs.tracer.enabled)
        self._batch_seq = 0  # serve thread only
        self._thread = threading.Thread(target=self._serve_loop,
                                        name="inference-server", daemon=True)
        self._thread.start()

    # -- client side -------------------------------------------------------

    def query(self, inputs: Any, timeout: float = 60.0) -> Any:
        """Blocking single-item query. inputs: pytree WITHOUT batch dim.

        Default timeout 60s (was 30): a 30s timeout once turned a
        single tens-of-seconds device stall into a fleet-wide cascade
        (actors exhausted restarts, the eval rotation died). Genuine
        server death still surfaces — just one stall-length later."""
        req = _Request(inputs)
        self._q.put(req)
        if not req.event.wait(timeout):
            raise TimeoutError("inference server did not reply")
        if isinstance(req.result, Exception):
            raise req.result
        return req.result

    def query_batch(self, inputs: Any, n: int, timeout: float = 60.0) -> Any:
        """Blocking multi-item query: every leaf of `inputs` carries a
        leading [n] batch dim; the reply's leaves do too. One request
        per vector-actor step — K env observations ride one queue entry
        and one scatter instead of K (SURVEY.md §2.4 "inference batching
        parallelism")."""
        assert n >= 1
        req = _Request(inputs, n)
        self._q.put(req)
        if not req.event.wait(timeout):
            raise TimeoutError("inference server did not reply")
        if isinstance(req.result, Exception):
            raise req.result
        return req.result

    def warmup(self, example_input: Any,
               extra_sizes: tuple[int, ...] = ()) -> None:
        """AOT-compile the batched forward at bucket sizes 1 and
        max_batch before actors start querying. On TPU the first compile
        takes 10-40s — longer than a reasonable query timeout — so an
        unwarmed server's first trickle of batch-1 queries times actors
        out (observed live: actor restart on 'inference server did not
        reply' during startup). Intermediate pow2 buckets still compile
        on first use, inside the 60s default query timeout.

        example_input: one request pytree WITHOUT the batch dim (content
        irrelevant; only shapes/dtypes feed the compile cache).
        extra_sizes: additional request sizes to pre-bucket (drivers pass
        envs_per_actor; a vector request larger than max_batch serves
        alone in its own bucket, which must therefore be warm too)."""
        with self._lock:
            params = self._params
        if self._slots is not None:
            self._warmup_slots(params)
            return
        # every bucket a pow2 REQUEST size up to max_batch can land in:
        # coalesced batches hit any of them (e.g. 2-3 K-item vector
        # requests -> bucket 2K/4K, truncation flushes -> small
        # buckets), and a cold intermediate bucket under load stalls
        # every queued actor behind one compile. Mapping _bucket over
        # request sizes (not doubling _bucket(1)) matters when the mesh
        # size is not a power of two: buckets are pow2 rounded up to a
        # mesh-size multiple, which doubling would skip.
        sizes = _pow2_bucket_sizes(self._bucket, self._max_batch,
                                   extra_sizes)
        # dedupe against already-warm buckets: an update_params epoch
        # bump changes VALUES, not shapes/dtypes, so re-warming after a
        # publication would re-pay every AOT compile for nothing
        # (asserted via the jit_compiles compile-telemetry delta)
        for b in sorted(sizes - self._warm_buckets):
            stacked = jax.tree.map(
                lambda x: np.zeros((b, *np.asarray(x).shape),
                                   np.asarray(x).dtype), example_input)
            if self._batched_sharding is not None:
                stacked = jax.device_put(stacked, self._batched_sharding)
            self._apply.lower(params, stacked).compile()
            self._warm_buckets.add(b)

    # -- learner side ------------------------------------------------------

    def update_params(self, params: Any, version: int) -> None:
        with self._lock:
            self._params = params
            self._params_version = version

    @property
    def params_version(self) -> int:
        with self._lock:
            return self._params_version

    @property
    def queue_depth(self) -> int:
        """Requests waiting right now — drivers log this around eval
        episodes to surface eval-induced actor back-pressure (the eval
        worker shares this server with the actors)."""
        return self._q.qsize()

    @property
    def stats(self) -> dict:
        return {"batches": self._batches_served,
                "items": self._items_served,
                "avg_batch": (self._items_served
                              / max(self._batches_served, 1))}

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)

    # a slot server's public reads (None / empty without slots); the
    # state and the ledger are the serve thread's: read them at rest

    @property
    def slot_state(self) -> Any:
        """The device pytree as the newest dispatch left it."""
        return None if self._slots is None else self._slots.state

    @property
    def slot_ledger(self) -> dict:
        pool = None if self._slots is None else self._slots.pool
        return {} if pool is None else {
            "slots_live": pool.live, "blocks_held": pool.blocks_held,
            "pool_blocks": pool.pool_blocks}

    @property
    def warm_buckets(self) -> list:
        """What `warmup()` has compiled: padded rows, or (tokens a row,
        padded rows) on the slot path."""
        return sorted(self._warm_buckets)

    def release_slots(self) -> None:
        """After `stop()`: give the device back what the sessions held."""
        for leaf in jax.tree.leaves(self._slots.state):
            leaf.delete()

    # -- server loop -------------------------------------------------------

    def _collect(self, block: bool = True) -> list[_Request]:
        # block=False (a batch is in flight, its reply owed): take only
        # what is ALREADY waiting — no wait for a first request, no
        # fill deadline. Otherwise as ever:
        # max_batch counts ITEMS, not requests: a vector actor's K-item
        # request fills K slots of the batch budget. A request that
        # would overflow the budget is HELD for a later batch (never
        # split) — otherwise a coalesced batch could exceed max_batch
        # and land in a bucket warmup never compiled (a 10-40s TPU
        # stall that times out every waiting actor). A single oversized
        # request still serves alone: its own bucket was warmed via
        # warmup's extra_sizes. Holding is NOT a barrier: a held-back
        # oversize request must not starve smaller requests that still
        # fit the current bucket, so non-fitting requests are parked
        # (arrival order preserved) while collection keeps admitting.
        reqs: list[_Request] = []
        items = 0
        kept: deque[_Request] = deque()
        while self._held:
            r = self._held.popleft()
            if (items + r.items <= self._max_batch
                    or (not reqs and r.items >= self._max_batch)):
                reqs.append(r)
                items += r.items
            else:
                kept.append(r)
        self._held = kept
        if not reqs:
            try:
                first = (self._q.get(timeout=0.05) if block
                         else self._q.get_nowait())
            except queue.Empty:
                return []
            reqs.append(first)
            items = first.items
        deadline = time.monotonic() + self._deadline_s
        while items < self._max_batch:
            try:
                if block:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    r = self._q.get(timeout=remaining)
                else:
                    r = self._q.get_nowait()
            except queue.Empty:
                break
            if items + r.items > self._max_batch:
                self._held.append(r)
                continue
            reqs.append(r)
            items += r.items
        return reqs

    def _collect_traced(self, block: bool = True) -> list[_Request]:
        """`_collect` as the `server.collect` span (the wait for the
        first request plus the fill deadline, or with a batch in flight
        the sweep of what is waiting; empty polls are not recorded) and
        one `server.queue_wait` interval per request, enqueue ->
        collected, tagged with the batch that serves it."""
        t0 = time.perf_counter()
        # the profiler sees every poll; the tracer only the ones that
        # produced a batch, so an idle server does not dilute the mean
        with jax.profiler.TraceAnnotation(ANNOTATION_PREFIX
                                          + "server.collect"):
            reqs = self._collect(block)
        if reqs:
            t1 = time.perf_counter()
            batch = self._batch_seq + 1
            self._obs.record("server.collect", t0, t1, batch=batch,
                             requests=len(reqs))
            for r in reqs:
                self._obs.record("server.queue_wait", r.t_enq, t1,
                                 batch=batch)
        return reqs

    def _serve_loop(self) -> None:
        """collect -> stack -> dispatch batch k+1, THEN fetch + scatter
        batch k (module docstring, "The serve loop")."""
        traced = self._traced
        collect = self._collect_traced if traced else self._collect
        flight: _Flight | None = None  # dispatched, its reply still owed
        # server.period (traced only): the serve thread's time a batch
        # answered, as laps of the tracer (wall always, the thread's
        # CPU clock when the name is due). One iteration while a
        # forward is in flight (dispatch k+1, reply k); an iteration
        # that only dispatched, because nothing was in flight, leaves
        # its period open for the next one, which sweeps the queue and
        # replies — so periods are batches answered. A period starts
        # where the last one ended (after an empty poll, at the loop's
        # top): they tile the thread's time but for its idle polls, and
        # the five children tile a period but for the loop's own
        # remainder
        lap = self._obs.lap
        since = None  # the open period's start; none is open
        dispatched = 0  # the seq the open period dispatched last
        while not self._stop.is_set():
            if traced and since is None:
                since = lap("server.period")
            # with a reply owed, never wait: not for a first request,
            # not for the fill deadline
            reqs = collect(flight is None)
            if flight is None and not reqs:
                # an idle-but-polling server is alive, not stalled: beat
                # so a wedged ACTOR gets the stall attribution instead of
                # the server it simply stopped querying
                self._obs.beat("inference-server", "idle")
                since = None
                continue
            ahead = self._dispatch(reqs) if reqs else None
            answered = 0  # the seq this iteration replied to
            if flight is not None:
                if ahead is not None and traced:
                    # the mechanism's own counter: one per batch that
                    # was dispatched with its predecessor unfetched
                    self._obs.record("server.ahead", ahead.t_dispatch,
                                     time.perf_counter(), batch=ahead.seq,
                                     behind=flight.seq)
                self._reply(flight)
                answered = flight.seq
            # the answered flight's last reference goes HERE, traced or
            # not: where its requests and device outputs are freed is
            # where the thread next waits for the interpreter (PERF.md
            # section 6, PR 52), so no local may keep it past this line
            flight = ahead
            if traced:
                if reqs:
                    dispatched = self._batch_seq
                if answered or ahead is None:
                    # `batch`: the seq the period dispatched (its last,
                    # where one that was left open dispatched two),
                    # `behind`: the seq it answered; 0 for none
                    since = lap("server.period", since, batch=dispatched,
                                behind=answered)
                    dispatched = 0
        if flight is not None:  # stop() leaves no client in event.wait
            self._reply(flight)
            if traced:
                lap("server.period", since, batch=dispatched,
                    behind=flight.seq)

    def _bucket(self, n: int) -> int:
        """Padded batch size: next pow2, rounded up to a multiple of the
        mesh size in sharded mode so every shard gets identical work."""
        b = next_pow2(max(n, 1))
        if b % self._min_bucket:
            b = -(-b // self._min_bucket) * self._min_bucket
        return b

    def _fail(self, reqs: list[_Request], e: Exception) -> None:
        """An error reaches the requests of the batch it belongs to,
        and only those; the server keeps serving."""
        # forensics: the error surfaces in the CALLERS' threads; the
        # ring keeps the server-side attribution
        self._obs.blackbox.record(
            "serve_error", component="inference-server",
            error=repr(e)[:200])
        for r in reqs:
            r.result = e
            r.event.set()

    def _dispatch(self, reqs: list[_Request]) -> _Flight | None:
        """First half of a batch: stack the requests, enqueue the
        forward and the copy of its outputs back to the host. Returns
        without waiting for either; None when the batch failed here
        (its requests have the error)."""
        n = sum(r.items for r in reqs)
        padded = self._bucket(n)
        span = self._obs.span
        self._batch_seq = seq = self._batch_seq + 1
        # server.batch is one batch end to end, stack start -> scatter
        # end, and overlaps its successor's first half; the four
        # children tile the serve thread's time (server.collect is the
        # fifth part of a period) and repeat its seq as `batch`
        t0 = time.perf_counter() if self._traced else 0.0
        try:
            with span("server.stack", batch=seq):
                # every request's leaves get a leading batch dim
                # (single-item requests gain one), then requests
                # concatenate
                leads = [r.inputs if r.n else
                         jax.tree.map(lambda x: np.asarray(x)[None],
                                      r.inputs)
                         for r in reqs]
                stacked = jax.tree.map(
                    lambda *xs: _pad_concat(xs, padded), *leads)
                if self._batched_sharding is not None:
                    stacked = jax.device_put(stacked,
                                             self._batched_sharding)
            t_dispatch = time.perf_counter() if self._traced else 0.0
            with span("server.dispatch", batch=seq):
                # host -> device copy of the batch, the enqueue, and
                # the device -> host copy queued right behind it
                with self._lock:
                    params = self._params
                    version = self._params_version
                out = self._apply(params, stacked)
                for leaf in jax.tree.leaves(out):
                    leaf.copy_to_host_async()
        except Exception as e:  # propagate to callers, keep serving
            self._fail(reqs, e)
            return None
        return _Flight(reqs, n, padded, seq, version, out, t0, t_dispatch)

    def _reply(self, flight: _Flight) -> None:
        """Second half of a batch: fetch its outputs and release its
        callers, with the version it was dispatched with."""
        reqs, seq = flight.reqs, flight.seq
        span = self._obs.span
        try:
            with span("server.fetch", batch=seq):
                # what is left of device time + device -> host, and the
                # wait for the GIL: next to nothing when a successor's
                # stack + dispatch ran in between
                out_np = jax.tree.map(np.asarray, flight.out)
            with span("server.scatter", batch=seq):
                off = 0
                t_done = time.perf_counter()
                for r in reqs:
                    if r.n:
                        lo, hi = off, off + r.n
                        r.result = jax.tree.map(lambda x: x[lo:hi],
                                                out_np)
                    else:
                        idx = off
                        r.result = jax.tree.map(lambda x: x[idx], out_np)
                    off += r.items
                    r.event.set()
                # end-to-end request latency (enqueue -> result ready):
                # the serving SLO — covers queue wait, batching
                # deadline, the forward, and the scatter, which is what
                # an actor actually blocks on. One histogram call per
                # batch, after every caller has been released
                self._obs.observe_many(
                    "infer_latency_ms",
                    [(t_done - r.t_enq) * 1e3 for r in reqs])
        except Exception as e:  # propagate to callers, keep serving
            self._fail(reqs, e)
            return
        if self._traced:
            self._obs.record("server.batch", flight.t0,
                             time.perf_counter(), items=flight.n,
                             padded=flight.padded, seq=seq)
        # stats() reads these from other threads; the serve thread is
        # the only writer but += is still a read-modify-write
        with self._lock:
            self._batches_served += 1
            self._items_served += flight.n
        self._obs.on_server_batch(flight.n, flight.version,
                                  self._q.qsize())



    # -- the slot path (module docstring) ------------------------------------

    def _rows_kind(self, r: _Request) -> int:
        """Tokens a row of request `r` brings: 1 (a decode step) or the
        prefill chunk."""
        decode = np.ndim(r.inputs["obs"]) == (1 if r.n else 0)
        return 1 if decode else self._slots.prefill_chunk

    def _collect_slots(self, block: bool = True) -> list[_Request]:
        """`_collect`, then: a batch holds rows of ONE kind (the first
        request's), at most `prefill_rows` of the prefill kind, and no
        slot twice - whatever else was collected waits, in arrival
        order, for the next batch. And WITH A REPLY OWED THAT THE DEVICE
        HAS NOT FINISHED (`block` False, nothing waiting): the wait for
        that reply is spent on the queue - a request that arrives while
        the step still runs is stacked and dispatched behind it, so a
        closed loop whose clients fall into groups keeps them: the
        groups take turns and the host's part of one group's period
        hides behind another's step (PERF.md section 6, PR 55: at the
        9B preset's 64 rows / 2 ms, p50 -8% and tokens/s +15% in every
        run against the loop without it, p99 inside its spread). The
        owed reply is late by the fill deadline at most, and only when
        the step ends inside that fill."""
        reqs = BatchedInferenceServer._collect(self, block)
        if not reqs and not block:
            owed = self._owed
            while (owed is not None and not owed.is_ready()
                   and not self._stop.is_set()):
                try:
                    self._held.append(self._q.get(timeout=0.0005))
                except queue.Empty:
                    continue
                reqs = BatchedInferenceServer._collect(self, True)
                break
        if len(reqs) < 2 and (not reqs or self._rows_kind(reqs[0]) == 1):
            return reqs
        kind = self._rows_kind(reqs[0])
        budget = self._max_batch if kind == 1 else self._slots.prefill_rows
        keep, later, seen, rows = [], [], set(), 0
        for r in reqs:
            slots = set(np.reshape(r.inputs["slot"], -1).tolist())
            if keep and (self._rows_kind(r) != kind or slots & seen
                         or rows + r.items > budget):
                later.append(r)
            else:
                keep.append(r)
                seen |= slots
                rows += r.items
        self._held.extendleft(reversed(later))
        return keep

    def _slot_bucket(self, n: int, kind: int) -> int:
        """Rows a dispatch of `n` rows is padded to: the next power of
        two, and no more than the kind's budget (a full batch pads no
        row: a padding row still reads and writes scratch state)."""
        most = self._max_batch if kind == 1 else self._slots.prefill_rows
        return max(min(self._bucket(n), most), n)

    def _admit(self, r: _Request, n_valid: np.ndarray) -> np.ndarray:
        """Request `r`'s rows into the ledger -> each row's first block.
        A session that begins is admitted (what its slot held is freed),
        every row's session grows by its tokens; on an error the ledger
        is as it was."""
        pool = self._slots.pool
        slot = np.reshape(r.inputs["slot"], -1)
        fresh = np.reshape(r.inputs["fresh"], -1)
        declared = np.reshape(r.inputs.get("max_len", np.zeros_like(slot)),
                              -1)
        before = {int(s): pool.held(int(s)) for s in slot}
        try:
            for s, f, d, n in zip(slot, fresh, declared, n_valid):
                if f:
                    gave = pool.free(int(s))
                    if gave:
                        self._obs.mark("server.slot_free", slot=int(s),
                                       blocks=gave)
                    base = pool.admit(int(s), int(d))
                    self._obs.mark("server.slot_admit", slot=int(s),
                                   base=base, declared=int(d))
                pool.advance(int(s), int(n))
        except Exception:
            for s, held in before.items():
                pool.restore(s, held)
            raise
        return np.asarray([pool.base(int(s)) for s in slot], np.int32)

    def _stack_slots(self, reqs: list[_Request], kind: int, padded: int
                     ) -> tuple[dict, list[_Request]]:
        """-> (the program's inputs for the requests that were admitted,
        those requests); one that was not has its error already."""
        pool = self._slots.pool
        rows, served = [], []
        for r in reqs:
            inp = r.inputs if r.n else jax.tree.map(
                lambda x: np.asarray(x)[None], r.inputs)
            n_valid = (np.asarray(inp["n_valid"], np.int32) if kind > 1
                       else np.ones(r.items, np.int32))
            try:
                base = self._admit(r, n_valid)
            except Exception as e:
                self._fail([r], e)
                continue
            rows.append({"obs": np.asarray(inp["obs"], np.int32),
                         "slot": np.asarray(inp["slot"], np.int32),
                         "fresh": np.asarray(inp["fresh"], np.int32),
                         "base": base, "n_valid": n_valid})
            served.append(r)
        if not served:
            return {}, served
        stacked = {k: np.concatenate([row[k] for row in rows])
                   for k in rows[0]}
        n = stacked["slot"].shape[0]
        # a padding row is a fresh session of no tokens in the scratch
        # slot
        fill = {"slot": pool.scratch_slot, "fresh": 1,
                "base": pool.scratch_base, "obs": 0, "n_valid": 0}
        return {k: np.concatenate([v, np.full(
            (padded - n, *v.shape[1:]), fill[k], v.dtype)])
            for k, v in stacked.items()}, served

    def _dispatch_slots(self, reqs: list[_Request]) -> _Flight | None:
        """`_dispatch` for a slot server: admission on the host, then
        the program with the state donated; the state it returns is the
        next dispatch's."""
        kind = self._rows_kind(reqs[0])
        n = sum(r.items for r in reqs)
        padded = self._slot_bucket(n, kind)
        span = self._obs.span
        self._batch_seq = seq = self._batch_seq + 1
        t0 = time.perf_counter() if self._traced else 0.0
        pool = self._slots.pool
        ledger = pool.snapshot()    # what the device's lengths agree with
        try:
            with span("server.stack", batch=seq, n=kind, rows=n):
                stacked, reqs = self._stack_slots(reqs, kind, padded)
            if not reqs:
                return None
            n = sum(r.items for r in reqs)
            t_dispatch = time.perf_counter() if self._traced else 0.0
            with span("server.dispatch", batch=seq, n=kind, rows=n):
                with self._lock:
                    params = self._params
                    version = self._params_version
                out, self._slots.state = self._apply(
                    params, self._slots.state, stacked)
                counters = out.pop("counters")
                if not any(r.inputs.get("want_sel") is not None
                           for r in reqs):
                    # (a net that selects nothing answers none)
                    out.pop("sel", None)
                for leaf in jax.tree.leaves((out, counters)):
                    leaf.copy_to_host_async()
                self._owed = out["q"]
            self._obs.gauge("server.slots_live", pool.live)
            self._obs.gauge("server.slot_blocks_held", pool.blocks_held)
        except Exception as e:  # propagate to callers, keep serving
            self._fail(reqs, e)
            pool.reset(ledger)  # the device's lengths did not move
            # a trace or compile error leaves the donated state whole;
            # a failed execution takes it along
            if any(leaf.is_deleted()
                   for leaf in jax.tree.leaves(self._slots.state)):
                self._lose_slot_state()
            return None
        return _Flight(reqs, n, padded, seq, version, out, t0, t_dispatch,
                       counters)

    def _lose_slot_state(self) -> None:
        """A failed dispatch took the state with it and left nothing to
        serve from: the state is made again, zeroed, and every session
        that was live is lost by name (`SlotStateLost`) until it begins
        again - the server keeps serving."""
        self._slots.state = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), self._state_spec)
        self._slots.pool.lose_all()
        self._obs.mark("server.slot_state_lost")

    def _reply_slots(self, flight: _Flight) -> None:
        """The program's counters into `slot_counters`, then `_reply`:
        a caller that has its answer finds the batch counted."""
        try:
            for name, value in flight.counters.items():
                self.slot_counters[name] = (
                    self.slot_counters.get(name, 0) + int(value))
        except Exception as e:  # the batch failed on the device: the
            # state it handed on is no state
            self._fail(flight.reqs, e)
            self._owed = None
            self._lose_slot_state()
            return
        BatchedInferenceServer._reply(self, flight)

    def _warmup_slots(self, params: Any) -> None:
        """Run every bucket of both kinds once on scratch rows: each
        compiles, and the state the program hands back stays the
        server's (a scratch row touches no session)."""
        plan = self._slots
        kinds = [(1, self._max_batch)]
        if plan.prefill_chunk > 1:
            kinds.append((plan.prefill_chunk, plan.prefill_rows))
        for kind, most in kinds:
            # every power of two under the budget, and the budget itself
            sizes = {self._slot_bucket(n, kind) for n in (
                *(1 << i for i in range(most.bit_length())), most)
                if n <= most}
            for rows in sorted(sizes):
                if (kind, rows) in self._warm_buckets:
                    continue
                shape = (rows,) if kind == 1 else (rows, kind)
                stacked = {
                    "obs": np.zeros(shape, np.int32),
                    "slot": np.full(rows, plan.pool.scratch_slot, np.int32),
                    "fresh": np.ones(rows, np.int32),
                    "base": np.full(rows, plan.pool.scratch_base, np.int32),
                    "n_valid": np.zeros(rows, np.int32)}
                out, plan.state = self._apply(params, plan.state, stacked)
                jax.block_until_ready(out)
                self._warm_buckets.add((kind, rows))


def _pad_concat(xs: tuple, padded: int) -> np.ndarray:
    arr = (np.asarray(xs[0]) if len(xs) == 1
           else np.concatenate([np.asarray(x) for x in xs]))
    if arr.shape[0] < padded:
        pad_width = [(0, padded - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
        arr = np.pad(arr, pad_width)
    return arr


def _pow2_bucket_sizes(bucket_fn: Callable[[int], int], max_batch: int,
                       extra_sizes: tuple[int, ...]) -> set[int]:
    """Every bucket a pow2 REQUEST size up to max_batch can land in:
    coalesced batches hit any of them (e.g. 2-3 K-item vector requests
    -> bucket 2K/4K, truncation flushes -> small buckets), and a cold
    intermediate bucket under load stalls every queued actor behind one
    compile. Mapping the bucket fn over request sizes (not doubling
    bucket(1)) matters when the mesh size is not a power of two:
    buckets are pow2 rounded up to a mesh-size multiple, which doubling
    would skip."""
    sizes = set()
    n = 1
    while n < max_batch:
        sizes.add(bucket_fn(n))
        n *= 2
    sizes.add(bucket_fn(max_batch))
    sizes.update(bucket_fn(s) for s in extra_sizes if s >= 1)
    return sizes


# -- multi-tenant serving tier (ISSUE 13) ----------------------------------


class ServeShed(RuntimeError):
    """Request shed by the admission controller: queue depth crossed
    the SLO line and this request sat in a sheddable (non-top) priority
    class. Attributed so the caller knows WHICH tenant lost work."""

    def __init__(self, policy_id: str, priority: int):
        super().__init__(
            f"request for policy {policy_id!r} (class {priority}) shed: "
            f"admission queue over the SLO line")
        self.policy_id = policy_id
        self.priority = priority


class ServeDeadlineExceeded(TimeoutError):
    """Request expired in the admission queue before dispatch. The
    timeout is ATTRIBUTED — it names the policy_id and class — so an
    overloaded tenant shows up in actor logs as itself, not as a
    generic server stall."""

    def __init__(self, policy_id: str, priority: int, waited_ms: float):
        super().__init__(
            f"request for policy {policy_id!r} (class {priority}) "
            f"expired after {waited_ms:.0f}ms in the admission queue")
        self.policy_id = policy_id
        self.priority = priority


class _ServeRequest:
    __slots__ = ("policy", "prio", "inputs", "n", "event", "result",
                 "t_enq")

    def __init__(self, policy: str, prio: int, inputs: Any, n: int = 0):
        self.policy = policy
        self.prio = prio
        self.inputs = inputs
        self.n = n
        self.event = threading.Event()
        self.result: Any = None
        self.t_enq = time.perf_counter()

    @property
    def items(self) -> int:
        return self.n if self.n else 1

    def wait(self, timeout: float = 60.0) -> Any:
        """Block until served; raises the attributed shed/deadline
        error if the admission controller rejected the request."""
        if not self.event.wait(timeout):
            raise TimeoutError("inference server did not reply")
        if isinstance(self.result, Exception):
            raise self.result
        return self.result


class _Policy:
    """One registered tenant: epoch-versioned params plus its row in
    the family's stacked param tree and per-tenant accounting."""

    __slots__ = ("policy_id", "family", "params", "version", "row",
                 "offered", "admitted", "shed", "pending_items",
                 "lat_ms")

    def __init__(self, policy_id: str, family: str, params: Any,
                 version: int, row: int):
        self.policy_id = policy_id
        self.family = family
        self.params = params
        self.version = version
        self.row = row
        self.offered = 0
        self.admitted = 0
        self.shed = 0
        self.pending_items = 0
        # recent end-to-end latencies (ms) for the per-tenant p50/p99
        # gauges; a bounded reservoir, appended only by the dispatch
        # thread, snapshotted by the stats publisher
        self.lat_ms: deque[float] = deque(maxlen=512)


class _Family:
    """One apply-fn family: the tenants it serves, their stacked param
    cache for the coalesced forward, per-class pending deques, and the
    warm-bucket memo for both forward paths."""

    __slots__ = ("name", "apply_plain", "apply_gather", "policies",
                 "stacked", "dirty", "pending", "pending_items",
                 "warm_plain", "warm_gather")

    def __init__(self, name: str, apply_plain: Callable,
                 apply_gather: Callable, classes: int):
        self.name = name
        self.apply_plain = apply_plain
        self.apply_gather = apply_gather
        self.policies: list[_Policy] = []
        self.stacked: Any = None
        self.dirty = True
        self.pending: list[deque[_ServeRequest]] = [
            deque() for _ in range(classes)]
        self.pending_items = 0
        self.warm_plain: set[int] = set()
        self.warm_gather: set[int] = set()


def _make_gather_apply(apply_fn: Callable) -> Callable:
    """Coalesced multi-tenant forward: params leaves carry a leading
    [n_policies] axis, `rows` maps each batch item to its tenant's
    row, and vmap over (gathered per-example params, batch) runs every
    head in ONE dispatch — 57 tenants never mean 57 forwards. The
    gather materializes per-example param rows, so it pays ~batch x
    head-params HBM; the intended regime is many small per-tenant
    heads over a shared torso."""

    def one(p: Any, x: Any) -> Any:
        out = apply_fn(p, jax.tree.map(lambda leaf: leaf[None], x))
        return jax.tree.map(lambda leaf: leaf[0], out)

    def run(stacked_params: Any, rows: Any, batch: Any) -> Any:
        per = jax.tree.map(lambda p: p[rows], stacked_params)
        return jax.vmap(one)(per, batch)

    return run


class TenantClient:
    """Per-tenant view of a MultiPolicyInferenceServer with the exact
    BatchedInferenceServer client/learner surface (query, query_batch,
    warmup, update_params, params_version, queue_depth, stats, stop),
    so drivers, actor hosts and the eval worker are tenant-tagged
    without signature changes. Every query it submits carries this
    view's (policy_id, priority class)."""

    def __init__(self, tier: "MultiPolicyInferenceServer",
                 policy_id: str, priority: int):
        self._tier = tier
        self.policy_id = policy_id
        self.priority = priority

    def submit(self, inputs: Any, n: int = 0) -> _ServeRequest:
        """Non-blocking admission: returns a ticket whose .wait()
        yields the result (or raises the attributed shed/deadline
        error). The open-loop path for benches and load generators."""
        return self._tier.submit(self.policy_id, self.priority,
                                 inputs, n)

    def query(self, inputs: Any, timeout: float = 60.0) -> Any:
        return self.submit(inputs).wait(timeout)

    def query_batch(self, inputs: Any, n: int,
                    timeout: float = 60.0) -> Any:
        assert n >= 1
        return self.submit(inputs, n).wait(timeout)

    def warmup(self, example_input: Any,
               extra_sizes: tuple[int, ...] = ()) -> None:
        self._tier.warmup(self.policy_id, example_input,
                          extra_sizes=extra_sizes)

    def update_params(self, params: Any, version: int) -> None:
        self._tier.update_params(self.policy_id, params, version)

    @property
    def params_version(self) -> int:
        return self._tier.policy_version(self.policy_id)

    @property
    def queue_depth(self) -> int:
        return self._tier.queue_depth

    @property
    def stats(self) -> dict:
        return self._tier.tenant_stats(self.policy_id)

    def stop(self) -> None:
        # views share the tier; stop is idempotent there
        self._tier.stop()


class MultiPolicyInferenceServer:
    """Continuous-batching multi-policy serving tier (module docstring
    has the architecture sketch).

    Threads: "serving-admission" drains the intake queue into
    per-family per-class pending deques, shedding from the lowest
    class when depth crosses `queue_slo_items` and driving the
    backpressure signal; "serving-dispatch" builds priority-ordered
    batches (class 0 first, FIFO within a class, oversize requests
    parked without head-of-line blocking) and runs one forward per
    batch — plain jit when the batch is single-tenant, the stacked/
    gather-indexed coalesced forward when tenants mix. Admission keeps
    running while a forward is in flight: capacity freeing IS the
    admission signal, there are no collect-then-serve rounds.

    The dispatch thread itself is serial (one forward fetched before
    the next is stacked): `BatchedInferenceServer`'s one-forward-ahead
    pipeline is NOT carried over, the module docstring says why."""

    def __init__(self, max_batch: int = 64, deadline_ms: float = 2.0,
                 *, mesh: Mesh | None = None, obs: Any = None,
                 priority_classes: int = 3, queue_slo_items: int = 256,
                 request_deadline_ms: float = 0.0,
                 stats_every_s: float = 1.0, coalesce: bool = True):
        """priority_classes: number of admission classes; class 0 is
        the top class and is NEVER shed. queue_slo_items: pending-item
        depth above which the admission controller sheds lower classes
        and engages backpressure (hysteresis: disengages at half).
        request_deadline_ms: per-request admission-queue deadline
        (0 disables); expiry raises ServeDeadlineExceeded naming the
        policy_id. coalesce: allow the stacked/gather-indexed
        multi-tenant forward (single-tenant batches always take the
        plain path). Mesh mode shards the plain path exactly like
        BatchedInferenceServer; the coalesced path runs unsharded."""
        assert priority_classes >= 1
        self._classes = int(priority_classes)
        self._max_batch = max_batch
        self._deadline_s = deadline_ms / 1000.0
        self._slo_items = int(queue_slo_items)
        self._req_deadline_s = request_deadline_ms / 1000.0
        self._stats_every_s = float(stats_every_s)
        self._coalesce = bool(coalesce)
        self._mesh = mesh
        if mesh is not None:
            self._batched_sharding = NamedSharding(
                mesh, P(tuple(mesh.axis_names)))
            self._params_sharding = NamedSharding(mesh, P())
            self._min_bucket = int(mesh.size)
        else:
            self._batched_sharding = None
            self._params_sharding = None
            self._min_bucket = 1
        self._q: queue.Queue[_ServeRequest] = queue.Queue()
        # _lock guards the registry, every pending deque, the stacked
        # param caches and all serve accounting; admission, dispatch,
        # register/update and stats readers all cross it
        self._lock = make_lock("serving_tier._lock")
        self._policies: dict[str, _Policy] = {}  # guarded-by: _lock
        self._families: dict[str, _Family] = {}  # guarded-by: _lock
        self._pending_items = 0  # guarded-by: _lock
        self._offered = 0  # guarded-by: _lock
        self._admitted = 0  # guarded-by: _lock
        self._shed_by_class = [0] * self._classes  # guarded-by: _lock
        self._expired = 0  # guarded-by: _lock
        self._batches_served = 0  # guarded-by: _lock
        self._items_served = 0  # guarded-by: _lock
        self._bp_engaged = False  # guarded-by: _lock
        self._stats_last = time.monotonic()  # dispatch thread only
        # transport hook: called with True/False on backpressure
        # transitions (engage when depth crosses the SLO line, release
        # at half); installed by the host before traffic, called from
        # the admission/dispatch threads
        self.on_backpressure: Callable[[bool], None] | None = None
        self._stop_evt = threading.Event()
        self._work = threading.Event()
        self._obs = obs if obs is not None else NULL_OBS
        self._obs.register("inference-server")
        self._admit_thread = threading.Thread(
            target=self._admit_loop, name="serving-admission",
            daemon=True)
        self._dispatch_thread = threading.Thread(
            target=self._dispatch_loop, name="serving-dispatch",
            daemon=True)
        self._admit_thread.start()
        self._dispatch_thread.start()

    # -- registry ----------------------------------------------------------

    def register_policy(self, policy_id: str, apply_fn: Callable,
                        params: Any, *, family: str = "default",
                        priority: int = 0,
                        version: int = 0) -> TenantClient:
        """Register one tenant and return its TenantClient view.

        Tenants sharing `family` must share apply semantics (same net
        applied to per-tenant params) — the family's jitted forwards
        come from the FIRST registration; only params differ per
        tenant. Registration invalidates the family's stacked-param
        cache and coalesced warm set (the stack gains a row, which is
        a new compile shape)."""
        with self._lock:
            if policy_id in self._policies:
                raise ValueError(f"policy {policy_id!r} already "
                                 f"registered")
            fam = self._families.get(family)
            if fam is None:
                if self._params_sharding is not None:
                    plain = jax.jit(
                        apply_fn,
                        in_shardings=(self._params_sharding,
                                      self._batched_sharding),
                        out_shardings=self._batched_sharding)
                else:
                    plain = jax.jit(apply_fn)
                fam = _Family(family, plain,
                              jax.jit(_make_gather_apply(apply_fn)),
                              self._classes)
                self._families[family] = fam
            pol = _Policy(policy_id, family, params, version,
                          row=len(fam.policies))
            fam.policies.append(pol)
            fam.dirty = True
            fam.warm_gather.clear()
            self._policies[policy_id] = pol
            n_tenants = len(self._policies)
        self._obs.gauge("serve_tenants", float(n_tenants))
        prio = min(max(int(priority), 0), self._classes - 1)
        return TenantClient(self, policy_id, prio)

    def update_params(self, policy_id: str, params: Any,
                      version: int) -> None:
        with self._lock:
            pol = self._policies[policy_id]
            pol.params = params
            pol.version = version
            # values changed, shapes did not: the stacked cache must
            # rebuild, the warm-bucket memos stay valid
            self._families[pol.family].dirty = True

    def policy_version(self, policy_id: str) -> int:
        with self._lock:
            return self._policies[policy_id].version

    def warmup(self, policy_id: str, example_input: Any,
               extra_sizes: tuple[int, ...] = ()) -> None:
        """AOT-compile this tenant's family at every bucket size a
        request can land in, deduped against the family's warm sets —
        re-warming after an epoch bump or for a same-family sibling
        tenant costs nothing. Warms the plain path always and the
        coalesced path once the family has >1 tenant (its stack shape
        includes the tenant count, so warm AFTER registering all
        same-family tenants)."""
        with self._lock:
            pol = self._policies[policy_id]
            fam = self._families[pol.family]
            params = pol.params
            n_pols = len(fam.policies)
            stacked = (self._stacked_locked(fam)
                       if self._coalesce and n_pols > 1 else None)
        sizes = _pow2_bucket_sizes(self._bucket, self._max_batch,
                                   extra_sizes)
        for b in sorted(sizes - fam.warm_plain):
            zeros = _zeros_like_batch(example_input, b)
            if self._batched_sharding is not None:
                zeros = jax.device_put(zeros, self._batched_sharding)
            fam.apply_plain.lower(params, zeros).compile()
            fam.warm_plain.add(b)
        if self._coalesce and n_pols > 1:
            for b in sorted(sizes - fam.warm_gather):
                zeros = _zeros_like_batch(example_input, b)
                rows = np.zeros(b, np.int32)
                fam.apply_gather.lower(stacked, rows, zeros).compile()
                fam.warm_gather.add(b)

    # -- client side -------------------------------------------------------

    def submit(self, policy_id: str, priority: int, inputs: Any,
               n: int = 0) -> _ServeRequest:
        prio = min(max(int(priority), 0), self._classes - 1)
        req = _ServeRequest(policy_id, prio, inputs, n)
        self._q.put(req)
        return req

    # -- admission controller ----------------------------------------------

    def _admit_loop(self) -> None:
        while not self._stop_evt.is_set():
            try:
                r = self._q.get(timeout=0.05)
            except queue.Empty:
                continue
            with self._lock:
                pol = self._policies.get(r.policy)
            if pol is None:
                r.result = KeyError(
                    f"unknown policy {r.policy!r}: not registered "
                    f"with this serving tier")
                r.event.set()
                continue
            shed: list[_ServeRequest] = []
            with self._lock:
                fam = self._families[pol.family]
                fam.pending[r.prio].append(r)
                fam.pending_items += r.items
                self._pending_items += r.items
                pol.pending_items += r.items
                pol.offered += 1
                self._offered += 1
                if self._pending_items > self._slo_items:
                    shed = self._shed_locked()
                transition = self._bp_transition_locked(bool(shed))
                depth = self._pending_items
            self._obs.count("serve_offered", 1)
            for s in shed:
                s.result = ServeShed(s.policy, s.prio)
                s.event.set()
                self._obs.count("serve_shed", 1)
            self._obs.gauge("serve_queue_items", float(depth))
            if transition is not None:
                self._fire_backpressure(transition)
            self._work.set()

    def _shed_locked(self) -> list[_ServeRequest]:
        """Shed newest-first from the lowest priority class until the
        pending depth is back under the SLO line. Class 0 is never
        shed: under pure top-class overload the queue stays deep and
        backpressure is the only relief valve."""
        shed: list[_ServeRequest] = []
        for cls in range(self._classes - 1, 0, -1):
            for fam in self._families.values():
                dq = fam.pending[cls]
                while dq and self._pending_items > self._slo_items:
                    r = dq.pop()
                    fam.pending_items -= r.items
                    self._pending_items -= r.items  # apexlint: unguarded(caller holds _lock)
                    pol = self._policies[r.policy]
                    pol.pending_items -= r.items
                    pol.shed += 1
                    self._shed_by_class[cls] += 1  # apexlint: unguarded(caller holds _lock)
                    shed.append(r)
            if self._pending_items <= self._slo_items:
                break
        return shed

    def _bp_transition_locked(self, shed_now: bool) -> bool | None:
        """Hysteresis on the backpressure signal: engage when depth
        crosses the SLO line (or shedding fired), release only once
        the queue drains to half the line. Returns the new state on a
        transition, None otherwise."""
        depth = self._pending_items
        if not self._bp_engaged and (shed_now
                                     or depth > self._slo_items):
            self._bp_engaged = True  # apexlint: unguarded(caller holds _lock)
            return True
        if self._bp_engaged and depth <= self._slo_items // 2:
            self._bp_engaged = False  # apexlint: unguarded(caller holds _lock)
            return False
        return None

    def _fire_backpressure(self, engaged: bool) -> None:
        self._obs.gauge("serve_backpressure", 1.0 if engaged else 0.0)
        # backpressure flips are exactly the "significant recent
        # events" a post-crash ring should narrate
        self._obs.blackbox.record("backpressure",
                                  component="inference-server",
                                  engaged=bool(engaged))
        cb = self.on_backpressure
        if cb is not None:
            cb(engaged)

    # -- dispatch ----------------------------------------------------------

    def _dispatch_loop(self) -> None:
        while not self._stop_evt.is_set():
            picked = self._take_batch()
            if picked is None:
                self._work.wait(timeout=0.005)
                self._work.clear()
                self._obs.beat("inference-server", "idle")
                self._maybe_publish_stats()
                continue
            fam, reqs, items = picked
            try:
                self._forward(fam, reqs, items)
            except Exception as e:  # propagate to callers, keep serving
                self._obs.blackbox.record(
                    "serve_error", component="inference-server",
                    error=repr(e)[:200])
                for r in reqs:
                    r.result = e
                    r.event.set()
            self._maybe_publish_stats()

    def _take_batch(self) -> tuple[_Family, list[_ServeRequest],
                                   int] | None:
        """Pick the family whose head-of-queue request is most urgent
        (highest class, then oldest) and build a batch from its
        pending deques, class 0 first, FIFO within a class, parking
        non-fitting requests in place (no head-of-line blocking).
        Dispatches immediately on a full batch; otherwise waits out
        the batching deadline from the oldest pending admit."""
        now = time.perf_counter()
        expired: list[_ServeRequest] = []
        batch: tuple[_Family, list[_ServeRequest], int] | None = None
        transition: bool | None = None
        with self._lock:
            expired = self._sweep_expired_locked(now)
            best: tuple[int, float, _Family] | None = None
            for fam in self._families.values():
                for cls, dq in enumerate(fam.pending):
                    if dq:
                        if (best is None
                                or (cls, dq[0].t_enq) < best[:2]):
                            best = (cls, dq[0].t_enq, fam)
                        break
            if best is not None:
                fam = best[2]
                oldest = min(dq[0].t_enq
                             for dq in fam.pending if dq)
                if (fam.pending_items >= self._max_batch
                        or now - oldest >= self._deadline_s
                        or self._stop_evt.is_set()):
                    reqs: list[_ServeRequest] = []
                    items = 0
                    for dq in fam.pending:
                        kept: deque[_ServeRequest] = deque()
                        while dq:
                            r = dq.popleft()
                            if (items + r.items <= self._max_batch
                                    or (not reqs
                                        and r.items >= self._max_batch)):
                                reqs.append(r)
                                items += r.items
                            else:
                                kept.append(r)
                        dq.extend(kept)
                        if items >= self._max_batch:
                            break
                    fam.pending_items -= items
                    self._pending_items -= items
                    for r in reqs:
                        pol = self._policies[r.policy]
                        pol.pending_items -= r.items
                        pol.admitted += 1
                    self._admitted += len(reqs)
                    batch = (fam, reqs, items)
            if expired or batch:
                transition = self._bp_transition_locked(False)
        for r in expired:
            r.result = ServeDeadlineExceeded(
                r.policy, r.prio, (now - r.t_enq) * 1e3)
            r.event.set()
            self._obs.count("serve_expired", 1)
            self._obs.count("serve_shed", 1)
        if batch is not None:
            self._obs.count("serve_admitted", len(batch[1]))
        if transition is not None:
            self._fire_backpressure(transition)
        return batch

    def _sweep_expired_locked(self, now: float) -> list[_ServeRequest]:
        """Deadline-aware shedding: pending deques are FIFO, so the
        expired requests are exactly the stale heads."""
        if self._req_deadline_s <= 0:
            return []
        expired: list[_ServeRequest] = []
        for fam in self._families.values():
            for cls, dq in enumerate(fam.pending):
                while dq and now - dq[0].t_enq > self._req_deadline_s:
                    r = dq.popleft()
                    fam.pending_items -= r.items
                    self._pending_items -= r.items  # apexlint: unguarded(caller holds _lock)
                    pol = self._policies[r.policy]
                    pol.pending_items -= r.items
                    pol.shed += 1
                    self._shed_by_class[cls] += 1  # apexlint: unguarded(caller holds _lock)
                    self._expired += 1  # apexlint: unguarded(caller holds _lock)
                    expired.append(r)
        return expired

    def _bucket(self, n: int) -> int:
        b = next_pow2(max(n, 1))
        if b % self._min_bucket:
            b = -(-b // self._min_bucket) * self._min_bucket
        return b

    def _stacked_locked(self, fam: _Family) -> Any:
        """(Re)build the family's stacked param cache if a tenant
        registered or published since the last forward. One jnp.stack
        per leaf per publication — never per batch. Caller holds
        _lock; update_params contention is publication-rate, so the
        device work under the lock is bounded and rare."""
        if fam.dirty:
            if len(fam.policies) == 1:
                fam.stacked = jax.tree.map(
                    lambda x: jnp.asarray(x)[None],
                    fam.policies[0].params)
            else:
                fam.stacked = jax.tree.map(
                    lambda *xs: jnp.stack(
                        [jnp.asarray(x) for x in xs]),
                    *[p.params for p in fam.policies])
            fam.dirty = False
        return fam.stacked

    def _forward(self, fam: _Family, reqs: list[_ServeRequest],
                 items: int) -> None:
        padded = self._bucket(items)
        with self._obs.span("server.batch", items=items,
                            padded=padded):
            leads = [r.inputs if r.n else
                     jax.tree.map(lambda x: np.asarray(x)[None],
                                  r.inputs)
                     for r in reqs]
            stacked = jax.tree.map(
                lambda *xs: _pad_concat(xs, padded), *leads)
            with self._lock:
                pols = [self._policies[r.policy] for r in reqs]
                version = max(p.version for p in pols)
                single = len({p.policy_id for p in pols}) == 1
                if single or not self._coalesce:
                    params = pols[0].params
                    stacked_params = None
                else:
                    params = None
                    stacked_params = self._stacked_locked(fam)
            if stacked_params is None:
                # single-tenant batch: plain (optionally mesh-sharded)
                # forward — identical to BatchedInferenceServer
                if self._batched_sharding is not None:
                    stacked = jax.device_put(stacked,
                                             self._batched_sharding)
                out = fam.apply_plain(params, stacked)
            else:
                # mixed tenants: one gather-indexed forward; padding
                # rows point at row 0 and compute discarded garbage
                rows = np.zeros(padded, np.int32)
                off = 0
                for r, p in zip(reqs, pols):
                    rows[off:off + r.items] = p.row
                    off += r.items
                out = fam.apply_gather(stacked_params, rows, stacked)
            out_np = jax.tree.map(np.asarray, out)
        off = 0
        t_done = time.perf_counter()
        for r, p in zip(reqs, pols):
            if r.n:
                lo, hi = off, off + r.n
                r.result = jax.tree.map(lambda x: x[lo:hi], out_np)
            else:
                idx = off
                r.result = jax.tree.map(lambda x: x[idx], out_np)
            off += r.items
            lat_ms = (t_done - r.t_enq) * 1e3
            self._obs.observe("infer_latency_ms", lat_ms)
            p.lat_ms.append(lat_ms)
            r.event.set()
        with self._lock:
            self._batches_served += 1
            self._items_served += items
            depth = self._pending_items
        self._obs.on_server_batch(items, version,
                                  depth + self._q.qsize())

    def _maybe_publish_stats(self) -> None:
        """Per-tenant serve/<tenant>/ gauges at stats cadence: p50/p99
        of the latency reservoir, pending depth, offered/admitted/shed
        counts. Dynamic keys by design (same policy as learn/<tenant>/
        — the report regroups them; apexlint cross-references only
        literal names)."""
        now = time.monotonic()
        if now - self._stats_last < self._stats_every_s:
            return
        self._stats_last = now
        with self._lock:
            snap = [(p.policy_id, list(p.lat_ms), p.pending_items,
                     p.offered, p.admitted, p.shed)
                    for p in self._policies.values()]
        for pid, lats, depth, offered, admitted, shed in snap:
            if lats:
                q50, q99 = np.percentile(np.asarray(lats), (50, 99))
                self._obs.gauge(f"serve/{pid}/p50_ms", float(q50))
                self._obs.gauge(f"serve/{pid}/p99_ms", float(q99))
            self._obs.gauge(f"serve/{pid}/queue_depth", float(depth))
            self._obs.gauge(f"serve/{pid}/offered", float(offered))
            self._obs.gauge(f"serve/{pid}/admitted", float(admitted))
            self._obs.gauge(f"serve/{pid}/shed", float(shed))

    # -- aggregate surface -------------------------------------------------

    @property
    def queue_depth(self) -> int:
        with self._lock:
            pending = self._pending_items
        return pending + self._q.qsize()

    @property
    def backpressure_engaged(self) -> bool:
        with self._lock:
            return self._bp_engaged

    def force_backpressure(self, engaged: bool) -> bool:
        """Externally set the backpressure flag (the remediation
        plane's queue-SLO actuator, runtime/remediation.py). Fires the
        same gauge + transport callback as the admission controller's
        own transitions; the controller keeps running, so if its
        depth-based hysteresis disagrees it re-transitions on the next
        shed/drain — the external setting is a nudge with a live
        fallback, not an override that can wedge. Returns False on a
        no-op (already in the requested state)."""
        with self._lock:
            if self._bp_engaged == bool(engaged):
                return False
            self._bp_engaged = bool(engaged)
        self._fire_backpressure(bool(engaged))
        return True

    @property
    def stats(self) -> dict:
        with self._lock:
            return {"offered": self._offered,
                    "admitted": self._admitted,
                    "shed": sum(self._shed_by_class),
                    "shed_by_class": list(self._shed_by_class),
                    "expired": self._expired,
                    "batches": self._batches_served,
                    "items": self._items_served,
                    "avg_batch": (self._items_served
                                  / max(self._batches_served, 1)),
                    "tenants": len(self._policies)}

    def tenant_stats(self, policy_id: str) -> dict:
        with self._lock:
            pol = self._policies[policy_id]
            lats = list(pol.lat_ms)
            out = {"offered": pol.offered, "admitted": pol.admitted,
                   "shed": pol.shed, "pending": pol.pending_items,
                   "version": pol.version}
        if lats:
            q50, q99 = np.percentile(np.asarray(lats), (50, 99))
            out["p50_ms"], out["p99_ms"] = float(q50), float(q99)
        return out

    def stop(self) -> None:
        if self._stop_evt.is_set():
            return
        self._stop_evt.set()
        self._work.set()
        self._admit_thread.join(timeout=5)
        self._dispatch_thread.join(timeout=5)
        # unblock anyone still waiting: queued and pending requests
        # fail loudly instead of hitting their full client timeout
        leftovers: list[_ServeRequest] = []
        while True:
            try:
                leftovers.append(self._q.get_nowait())
            except queue.Empty:
                break
        with self._lock:
            for fam in self._families.values():
                for dq in fam.pending:
                    leftovers.extend(dq)
                    dq.clear()
                fam.pending_items = 0
            self._pending_items = 0
        for r in leftovers:
            if not r.event.is_set():
                r.result = RuntimeError("serving tier stopped")
                r.event.set()


def _zeros_like_batch(example_input: Any, b: int) -> Any:
    return jax.tree.map(
        lambda x: np.zeros((b, *np.asarray(x).shape),
                           np.asarray(x).dtype), example_input)


def build_serving_tier(serving: Any, *, max_batch: int,
                       deadline_ms: float, mesh: Mesh | None = None,
                       obs: Any = None) -> MultiPolicyInferenceServer:
    """Construct the serving tier from a configs.ServingConfig — the
    single place every serving knob is consumed, so drivers and actor
    hosts stay one-call sites."""
    return MultiPolicyInferenceServer(
        max_batch=max_batch, deadline_ms=deadline_ms, mesh=mesh,
        obs=obs,
        priority_classes=serving.priority_classes,
        queue_slo_items=serving.queue_slo_items,
        request_deadline_ms=serving.request_deadline_ms,
        stats_every_s=serving.stats_every_s,
        coalesce=serving.coalesce)
