"""Traffic kind `fleet_closed_loop`: what one chip sees from a fleet
of vector actors, minus the env stepping and n-step building an actor
core pays.

`clients` threads each run a closed loop (an actor waits for its
actions): `server.query_batch(obs, obs_per_query)` on seeded uint8
observations from a pre-built pool, then `segments_per_reply`
one-segment messages through `transport.send_experience` — the dict
`FrameSegmentBuilder._emit` + `VectorActor._ship` produce. So one
transition enters replay per forward, as in acting.

The system under test is the program's own `ApexDriver(cfg)` with no
actor threads and its default `LoopbackTransport`: its ingest loop,
stager, paced learner loop and param publishes, all started through
`run()` on a thread of the harness. Two facts of `run()` shape this
file: with no actor threads it returns as soon as the queue is empty
and the learner can make no progress on two polls 0.2 s apart, so
traffic never pauses from before `run()` until the stop; and its AOT
warm-up runs inside it, so set-up ends only after it.

Set-up: build pools, start `run()`, fill the ring through the public
ingest path (big messages, throttled on `transport.pending` so none is
dropped), warm the three server buckets 16-obs queries can land in,
start the clients, wait `settle_s` and until the learner has caught up
with its pacing. Then the fence, then the window.

Every shipped frame carries a stamp in its first eight pixels (message
serial, pool entry, frame number), so that any transition the learner
later samples can be traced to what was sent and compared byte for
byte.

Parameters (benchmarks/traffic/<mix>.json): `clients`,
`obs_per_query`, `segments_per_reply`, `think_ms`, `ring_fill`,
`fill_segments_per_message`, `fill_max_pending`, `obs_pool`,
`segment_pool`, `settle_s`, `settle_max_s`, `query_timeout_s`, `trace_window_s`.
"""

from __future__ import annotations

import os
import threading
import time

import jax
import numpy as np

from ape_x_dqn_tpu.runtime.driver import ApexDriver
from ape_x_dqn_tpu.runtime.train import apply_overrides
from ape_x_dqn_tpu.utils.metrics import Metrics
from benchmarks.harness import correctness, learner_checks
from benchmarks.harness import ring_content as rc
from benchmarks.harness.device import say

STAMP = 8          # bytes: serial u32 LE, pool entry u16 LE, frame, magic
STAMP_MAGIC = 0xA5
BIG = 10**8        # a finite max_grad_steps no window can reach


class LossLog(Metrics):
    """The driver's own metrics sink, keeping every loss it logs."""

    def __init__(self):
        super().__init__()
        self.losses: list = []

    def log(self, step: int, **scalars) -> None:
        if "loss" in scalars:
            self.losses.append(scalars["loss"])
        super().log(step, **scalars)


class Pools:
    """Seeded observations and segments, built once; messages are
    copies of pool entries with a stamp."""

    def __init__(self, geom: rc.Geometry, seed: int, n_obs: int,
                 n_seg: int):
        rng = np.random.default_rng(seed)
        g = geom
        self.geom = g
        self.obs = rng.integers(0, 256, (n_obs, g.height, g.width,
                                         g.stack), dtype=np.uint8)
        self.seg_frames = rng.integers(
            0, 256, (n_seg, g.frames, g.height, g.width), dtype=np.uint8)
        self.action = rng.integers(0, g.num_actions, (n_seg, g.seg)
                                   ).astype(np.int32)
        self.reward = rng.integers(-1, 2, (n_seg, g.seg)
                                   ).astype(np.float32)
        terminal = rng.integers(0, 64, (n_seg, g.seg)) == 0
        self.discount = np.where(terminal, 0.0, g.gamma ** g.n_step
                                 ).astype(np.float32)
        self.next_off = np.full((n_seg, g.seg), g.n_step, np.int32)
        self.priorities = (0.1 * rng.lognormal(0.0, 1.0, (n_seg, g.seg))
                           ).astype(np.float32)

    def message(self, entries: np.ndarray, serials: np.ndarray,
                actor: int) -> dict:
        """One ingest message of len(entries) stamped segments."""
        g = self.geom
        frames = self.seg_frames[entries]          # fancy index: a copy
        stamp = np.empty((len(entries), g.frames, STAMP), np.uint8)
        stamp[:, :, 0:4] = serials.astype("<u4").view(np.uint8).reshape(
            -1, 1, 4)
        stamp[:, :, 4:6] = entries.astype("<u2").view(np.uint8).reshape(
            -1, 1, 2)
        stamp[:, :, 6] = np.arange(g.frames, dtype=np.uint8)
        stamp[:, :, 7] = STAMP_MAGIC
        frames[:, :, 0, :STAMP] = stamp
        return {"seg_frames": frames,
                "action": self.action[entries],
                "reward": self.reward[entries],
                "discount": self.discount[entries],
                "next_off": self.next_off[entries],
                "priorities": self.priorities[entries],
                "actor": actor,
                "frames": len(entries) * g.seg}

    def expected(self, shard, local, items: dict) -> dict:
        """From the stamps of a drawn batch to what must be there:
        reads (pool entry, frame number) off each obs channel's stamp
        and rebuilds both stacks and the fields from the pool. A stamp
        that does not parse yields zeros, which cannot match."""
        g = self.geom
        slot = local % g.seg
        out = {k: np.zeros_like(np.asarray(items[k]))
               for k in ("obs", "next_obs", "action", "reward",
                         "discount")}
        obs = np.asarray(items["obs"])
        for i in range(obs.shape[0]):
            st = np.ascontiguousarray(obs[i, 0, :STAMP, 0])
            entry = int(st[4]) | (int(st[5]) << 8)
            if (st[7] != STAMP_MAGIC or st[6] != slot[i]
                    or entry >= self.seg_frames.shape[0]):
                continue
            serial = st[0:4]
            j = int(slot[i])
            for key, first in (("obs", j),
                               ("next_obs",
                                j + int(self.next_off[entry, j]))):
                planes = self.seg_frames[entry, first:first + g.stack
                                         ].copy()
                for c in range(g.stack):
                    planes[c, 0, 0:4] = serial
                    planes[c, 0, 4:6] = st[4:6]
                    planes[c, 0, 6] = first + c
                    planes[c, 0, 7] = STAMP_MAGIC
                out[key][i] = np.moveaxis(planes, 0, -1)
            out["action"][i] = self.action[entry, j]
            out["reward"][i] = self.reward[entry, j]
            out["discount"][i] = self.discount[entry, j]
        return out


class Client(threading.Thread):
    """One vector actor's traffic: query, then ship, in a closed loop."""

    def __init__(self, index: int, fleet: "Fleet"):
        super().__init__(name=f"bench-client-{index}", daemon=True)
        self.index = index
        self.fleet = fleet
        self.rng = np.random.default_rng([fleet.seed, 1000 + index])
        self.latencies_ms: list[tuple[float, float]] = []  # (t, ms)
        self.queries = 0
        self.query_failures = 0
        self.sent = 0             # messages shipped
        self.cpu_s = 0.0

    def run(self) -> None:
        f = self.fleet
        n, pools = f.obs_per_query, f.pools
        n_obs, n_seg = pools.obs.shape[0], pools.seg_frames.shape[0]
        annotate = jax.profiler.TraceAnnotation
        c0 = time.thread_time()
        while not f.stop.is_set():
            off = int(self.rng.integers(0, n_obs - n + 1))
            t0 = time.monotonic()
            try:
                with annotate("bench.client_query"):
                    f.server.query_batch(pools.obs[off:off + n], n,
                                         timeout=f.query_timeout_s)
            except Exception:  # noqa: BLE001 - counted, run goes on
                self.query_failures += 1
            t1 = time.monotonic()
            self.queries += 1
            self.latencies_ms.append((t1, (t1 - t0) * 1e3))
            for _ in range(f.segments_per_reply):
                entry = np.asarray([self.rng.integers(0, n_seg)])
                serial = np.asarray([(self.index << 24) | (self.sent
                                                           & 0xFFFFFF)])
                with annotate("bench.client_ship"):
                    f.transport.send_experience(
                        pools.message(entry, serial, self.index))
                self.sent += 1
            if f.think_s:
                time.sleep(f.think_s)
            self.cpu_s = time.thread_time() - c0


class Fleet:
    def __init__(self, driver, pools: Pools, params: dict, seed: int):
        self.server = driver.server
        self.transport = driver.transport
        self.pools = pools
        self.seed = seed
        self.obs_per_query = int(params["obs_per_query"])
        self.segments_per_reply = int(params["segments_per_reply"])
        self.think_s = float(params["think_ms"]) / 1e3
        self.query_timeout_s = float(params["query_timeout_s"])
        self.stop = threading.Event()
        self.clients = [Client(i, self)
                        for i in range(int(params["clients"]))]

    def totals(self) -> dict:
        return {"queries": sum(c.queries for c in self.clients),
                "query_failures": sum(c.query_failures
                                      for c in self.clients),
                "offered": self.pools.geom.seg * sum(
                    c.sent for c in self.clients),
                "cpu_s": sum(c.cpu_s for c in self.clients)}


def _fill(rt, driver, pools: Pools) -> dict:
    """Fill `ring_fill` of the ring through the public ingest path.
    Waits (sending nothing new) while `transport.pending` is at the
    throttle, so nothing is dropped; returns when the driver's own
    `ingest_rows` says everything landed. Messages are built once and
    only re-stamped: a buffer is reused after more sends than the
    queue can hold, when the stager has long copied it."""
    p = rt.params
    per_msg = int(p["fill_segments_per_message"])
    throttle = int(p["fill_max_pending"])
    want = int(driver.capacity * float(p["ring_fill"]))
    seg = pools.geom.seg
    messages = want // (per_msg * seg)
    rng = np.random.default_rng([rt.seed, 999])
    entries = rng.integers(0, pools.seg_frames.shape[0], per_msg)
    serial0 = np.arange(per_msg)
    rotation = [pools.message(entries, serial0, actor=-1)
                for _ in range(throttle + 4)]
    # the clock starts when the first block lands: until then the
    # driver is still compiling its warm-up and the queue just waits
    first: tuple[float, float] | None = None

    def landed() -> float:
        nonlocal first
        total = driver.ingest_rows.total
        if first is None and total > 0:
            first = (time.monotonic(), total)
        return total

    for m in range(messages):
        while driver.transport.pending >= throttle:
            landed()
            time.sleep(0.001)
        msg = dict(rotation[m % len(rotation)])
        serials = (0xFF << 24) | ((m * per_msg + serial0) & 0xFFFFFF)
        msg["seg_frames"][:, :, 0, 0:4] = serials.astype("<u4").view(
            np.uint8).reshape(-1, 1, 4)
        driver.transport.send_experience(msg)
    offered = messages * per_msg * seg
    while landed() < offered:
        time.sleep(0.005)
    return {"offered": offered, "transitions": offered - first[1],
            "seconds": time.monotonic() - first[0]}


def _percentiles(lat_ms: np.ndarray) -> dict:
    if lat_ms.size == 0:
        return {}
    return {"p50": float(np.percentile(lat_ms, 50)),
            "p99": float(np.percentile(lat_ms, 99)),
            "max": float(lat_ms.max()), "count": int(lat_ms.size)}


def _snapshot(driver, fleet: Fleet, tracer) -> dict:
    s = driver.server.stats
    return {"t": time.monotonic(),
            "grad_steps": driver.grad_steps.total,
            "frames": driver.frames.total,
            "added": driver.ingest_rows.total,
            "transport_dropped": driver.transport.dropped,
            "server_batches": s["batches"], "server_items": s["items"],
            "spans": tracer.aggregates(), **fleet.totals()}


def _server_matches_reference(rt, driver, pools: Pools):
    """(5a) Q-values the server returns for 256 pool observations match
    the reference on the params version served. Publishes race the
    queries, so the versions are read around them and the comparison
    retried if one landed in between."""
    n = int(rt.params["obs_per_query"])
    obs = pools.obs[:256]
    for _ in range(8):
        v0 = driver.server.params_version
        q = np.concatenate([
            np.asarray(driver.server.query_batch(obs[i:i + n], n,
                                                 timeout=20.0))
            for i in range(0, obs.shape[0], n)])
        params, v = driver.transport.get_params()
        if v0 == v == driver.server.params_version:
            ref_params = correctness.reference_params(
                jax.device_get(params), rt.sizes["cnn_strides"])
            ok, notes = correctness.q_values_match(ref_params, obs, q)
            notes["params_version"] = int(v)
            return ok, notes
    return False, {"error": "no quiet moment between param publishes"}


def run(rt) -> dict:
    cfg = rt.run_config()
    if rt.trace:
        # the program's own spans (replay.add, learner.train) exist
        # only with obs on, which also adds a block_until_ready to the
        # learner loop — so only the traced run pays for it
        os.makedirs(rt.trace_dir, exist_ok=True)
        cfg = apply_overrides(cfg, [
            "obs.enabled=true", "obs.blackbox=false",
            "obs.trace_path=" + os.path.join(rt.trace_dir,
                                             "program_spans.json")])
    p = rt.params
    say("imports done; building ApexDriver")
    loss_log = LossLog()
    driver = ApexDriver(cfg, metrics=loss_log)
    say("driver built; building pools")
    geom = rc.geometry(cfg, driver.spec)
    pools = Pools(geom, rt.seed, int(p["obs_pool"]),
                  int(p["segment_pool"]))
    fleet = Fleet(driver, pools, p, rt.seed)
    cap = cfg.learner.steps_per_frame_cap
    result: dict = {}

    def drive() -> None:
        try:
            result["summary"] = driver.run(max_grad_steps=BIG)
        except BaseException as e:  # noqa: BLE001 - reported below
            result["error"] = e
            raise

    # the queue must hold traffic before run() finishes its warm-up, or
    # run() sees an idle system and returns: the fill thread starts
    # first and parks on the throttle until ingest begins to drain
    filled: dict = {}
    filler = threading.Thread(
        target=lambda: filled.update(_fill(rt, driver, pools)),
        name="bench-fill", daemon=True)
    runner = threading.Thread(target=drive, name="bench-driver-run",
                              daemon=True)
    filler.start()
    runner.start()
    # warm the server buckets 16-obs requests coalesce into (16, 32,
    # 64) by real queries, while the driver warms its own graphs: the
    # driver skips server.warmup when it has no local actors
    n = fleet.obs_per_query
    for k in (1, 2, 4):
        if k * n <= cfg.inference.max_batch:
            driver.server.query_batch(
                np.concatenate([pools.obs[:n]] * k), k * n, timeout=600.0)
    say("server buckets warm")
    # clients start before the fill ends so traffic never pauses
    while filler.is_alive() and driver.ingest_rows.total < (
            0.95 * driver.capacity * float(p["ring_fill"])):
        if not runner.is_alive():
            raise RuntimeError(f"driver.run() ended during set-up: "
                               f"{result}")
        time.sleep(0.01)
    for c in fleet.clients:
        c.start()
    filler.join()
    say(f"filled {filled['offered']} transitions through the ingest "
        f"path, {filled['transitions']} of them in "
        f"{filled['seconds']:.2f}s after the first block landed")
    # settle: at least settle_s of steady traffic, then until the
    # learner has worked off what the fill's frames let it owe (at
    # most settle_max_s: a learner that cannot catch up is a result,
    # and `driver.pace_debt` reports it)
    t_settle = time.monotonic()
    while (time.monotonic() - t_settle < float(p["settle_s"])
           or (driver.grad_steps.total
               < cap * driver.frames.total - 2 * cfg.learner.train_chunk
               and time.monotonic() - t_settle
               < float(p["settle_max_s"]))):
        if not runner.is_alive():
            raise RuntimeError(f"driver.run() ended while settling: "
                               f"{result}")
        time.sleep(0.05)
    fence = jax.jit(lambda x: x + 1)
    fence(np.float32(0)).block_until_ready()
    rt.setup_done()

    tracer = driver.obs.tracer
    with rt.window():
        snap0 = _snapshot(driver, fleet, tracer)
        time.sleep(rt.seconds)
        fence(np.float32(0)).block_until_ready()
        snap1 = _snapshot(driver, fleet, tracer)
    window_s = snap1["t"] - snap0["t"]
    if not runner.is_alive():
        raise RuntimeError(f"driver.run() ended inside the window: "
                           f"{result}")

    q_ok, q_notes = _server_matches_reference(rt, driver, pools)
    # stop: clients first, let ingest drain the queue, then the driver
    fleet.stop.set()
    for c in fleet.clients:
        c.join(timeout=30.0)
    while driver.transport.pending and runner.is_alive():
        time.sleep(0.002)
    driver.stop_event.set()
    runner.join(timeout=60.0)
    if "summary" not in result:
        raise RuntimeError(f"driver.run() did not return: {result}")
    summary = result["summary"]

    totals = fleet.totals()
    offered = filled["offered"] + totals["offered"]
    added = int(driver.ingest_rows.total)
    # the fill is throttled and never dropped, so every dropped
    # message is a client's: one segment
    transport_dropped = driver.transport.dropped * geom.seg
    stage_dropped = int(summary["ingest_dropped"]
                        - driver.transport.dropped)
    dropped = transport_dropped + stage_dropped
    lost = offered - added - dropped

    d = {k: snap1[k] - snap0[k] for k in snap0 if k != "spans"}
    lat = np.asarray([ms for c in fleet.clients
                      for t, ms in c.latencies_ms
                      if snap0["t"] <= t <= snap1["t"]])
    pct = _percentiles(lat)
    # transitions that reached the stager (past the transport's drop
    # point; every full block ships) count in units of one segment,
    # not of one 1,024-transition add_many. The learner dispatches
    # whole chunks ahead of its pacing, so its count is allowed the
    # one dispatch a window edge can cut off
    paced = min(d["frames"],
                (d["grad_steps"] + cfg.learner.train_chunk) / cap)
    spans = {}
    for name, a1 in snap1["spans"].items():
        a0 = snap0["spans"].get(name, {"count": 0, "total_s": 0.0})
        spans[name] = {"count": a1["count"] - a0["count"],
                       "total_ms": (a1["total_s"] - a0["total_s"]) * 1e3}

    state, checks, notes = learner_checks.check_learner(
        driver.learner, driver.state, cfg, driver.dp,
        rt.sizes["cnn_strides"], pools.expected)
    # the driver logs a loss every 100 grad steps and the last one
    losses = loss_log.losses + [summary["loss"]]
    checks["server_q_matches_reference"] = q_ok
    checks["offered_is_added_plus_dropped"] = lost == 0
    checks["every_loss_finite"] = all(
        x is not None and np.isfinite(x) for x in losses)
    checks["no_loop_errors"] = not (summary["loop_errors"]
                                    or summary["actor_errors"])
    say("check notes " + repr({**notes, **q_notes}))
    say(f"window {window_s:.3f}s: {d['queries']} queries "
        f"({d['query_failures']} failed), latency {pct}; offered "
        f"{d['offered']} added {d['added']} transitions, "
        f"{d['grad_steps']:.0f} grad steps "
        f"({d['grad_steps'] / window_s:.1f}/s), {d['frames']:.0f} "
        f"frames; generator CPU share "
        f"{d['cpu_s'] / window_s:.2f} cores; server "
        f"{d['server_items'] / max(d['server_batches'], 1):.1f} "
        f"obs/batch over {d['server_batches']} batches")
    say(f"ledger whole run: offered {offered} = added {added} + "
        f"dropped {dropped} (transport {transport_dropped}, stager "
        f"{stage_dropped}) + lost {lost}")
    return {
        "attempted": int(d["queries"] + d["offered"]),
        # a counted drop is the system's stated behaviour, not a
        # failure; a transition neither added nor counted is one
        "failed": int(d["query_failures"] + max(lost, 0)),
        "checks": checks,
        "end_to_end": {
            "fleet_transitions_per_s": paced / window_s,
            # a failed or timed-out query counts as over any limit: it
            # stays in the sample with the time it took to fail
            "infer_p99_ms": pct.get("p99"),
        },
        "window_s": window_s,
        "window_counters": {"grad_steps": d["grad_steps"],
                            "frames": d["frames"], "added": d["added"]},
        "steps_per_frame_cap": cap,
        "ingest_ledger": {"offered": offered, "added": added,
                          "dropped": dropped},
        "server_window": {"batches": d["server_batches"],
                          "items": d["server_items"]},
        "query_latency_ms": pct,
        "program_spans": spans,
        "fill": filled,
        "generator_cpu_cores": d["cpu_s"] / window_s,
        "family": rt.cell.config["family"],
    }
