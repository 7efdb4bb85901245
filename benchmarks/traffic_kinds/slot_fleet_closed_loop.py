"""Traffic kind `slot_fleet_closed_loop`: what an actor host's chip sees
from a WIDE fleet of agents a few thousand tokens into their episodes -
the inference server ALONE, as `runtime/actor_host.run_actor_host`
builds it (the family's `server_apply_fn` and `server_slots`, the
server's own `warmup()`), for a net the server keeps in slots and that
SELECTS NOTHING. `slot_sessions_closed_loop.py` reads `out["sel"]` of
every checked session and hands its check a `sel` array, and may not be
edited; this kind stands beside it and imports what it can from it
(`draw_sessions`, `checked_slots`, `_bfloat16_params`; through it
`fleet_closed_loop._percentiles`): one definition each.

`clients` threads x `sessions_per_client` sessions = that many slots.
Each session's starting context is drawn log-uniform from `start_min` ..
`start_max` tokens from `--seed`, token ids Zipf(`token_zipf_exponent`)
over the vocabulary, and it declares `start + decode_max` positions at
its first query.

SET-UP: build the net, seed its parameters and round the matrices ONCE
to bfloat16, build and warm the server, then every client prefills its
own sessions through the server's `query_batch` in chunks of
`inference.prefill_chunk` tokens, one row a session that still has
prompt left. Clients then start their decode loops; `settle_s` later,
the fence.

WINDOW: a closed loop with no think time - every client sends one
`query_batch` of its sessions' newest tokens (one token a session),
takes `q`, acts greedily, makes each next token by `SyntheticTokens`'
rule (`(a + 31 o + 7) mod V`), sends again. `infer_p99_ms` is PERF.md
section 2's: client side, send to reply, over all the window's queries.

`correct`: the check the configuration's file names (`checks`: the
net's reference, its mapper and the check, imported BY NAME) on what the
timed path answered and left on the device, for the sessions at
`checked_quantiles` of the seed's starting lengths: the `q` of their
last `checked_steps` decode steps of the window, and the state the
server holds of them when the run stops, against the reference over the
session's whole history; and the server's counters against every
session's length. Plus: no query failed, the server's `extend_tokens`
counter equals the tokens sent, and every session's length on the
device is what was sent.

Parameters (benchmarks/traffic/<mix>.json): `clients`,
`sessions_per_client`, `start_min`, `start_max`, `decode_max`,
`token_zipf_exponent`, `settle_s`, `query_timeout_s`,
`checked_quantiles`, `checked_steps`, `trace_window_s`, `show_limits`.
"""

from __future__ import annotations

import importlib
import os
import threading
import time
from collections import deque

import jax
import numpy as np

from ape_x_dqn_tpu.envs import make_env
from ape_x_dqn_tpu.models import build_network, decoder_block
from ape_x_dqn_tpu.obs import build_obs
from ape_x_dqn_tpu.parallel.inference_server import BatchedInferenceServer
from ape_x_dqn_tpu.runtime import family as fam
from ape_x_dqn_tpu.runtime.train import apply_overrides
from ape_x_dqn_tpu.utils.metrics import Metrics
from ape_x_dqn_tpu.utils.rng import component_key
from benchmarks.harness.device import say
from benchmarks.traffic_kinds import slot_sessions_closed_loop as sessions_kind

# from the kind this one stands beside: its draws, its rounding of the
# parameters and, through it, the fleet kind's percentiles
draw_sessions = sessions_kind.draw_sessions
checked_slots = sessions_kind.checked_slots
_bfloat16_params = sessions_kind._bfloat16_params
_percentiles = sessions_kind.fleet_closed_loop._percentiles


class Client(threading.Thread):
    """One actor thread's traffic: its sessions' prefill, then one
    token a session a query, in a closed loop."""

    def __init__(self, index: int, server, prompts: list[np.ndarray],
                 p: dict, chunk: int, vocab: int, checked: set[int]):
        super().__init__(name=f"bench-client-{index}", daemon=True)
        k = len(prompts)
        self.server, self.prompts, self.chunk = server, prompts, chunk
        self.vocab, self.timeout = vocab, float(p["query_timeout_s"])
        self.slots = np.arange(index * k, (index + 1) * k, dtype=np.int32)
        # the sessions of this client that `correct` reads: row -> slot
        self.checked = {r: int(s) for r, s in enumerate(self.slots)
                        if int(s) in checked}
        self.keep = int(p["checked_steps"])
        self.stop = threading.Event()
        self.go = threading.Event()         # prefill done, decode may start
        self.prefilled = threading.Event()
        self.latencies_ms: list[tuple[float, float]] = []
        self.queries = self.failures = self.tokens_sent = 0
        self.error: Exception | None = None
        # per checked slot: the decoded tokens and the newest `keep`
        # (time, position, q) - more than `keep`: the loop runs a step
        # or two past the window
        self.decoded = {s: [] for s in self.checked.values()}
        self.recent = {s: deque(maxlen=self.keep + 8)
                       for s in self.checked.values()}
        self.newest = np.zeros(k, np.int32)
        self.length = np.asarray([len(x) for x in prompts], np.int64)
        # what each declared
        self.limit = self.length + int(p["decode_max"])

    def _prefill(self) -> None:
        k, chunk = len(self.prompts), self.chunk
        done = np.zeros(k, np.int64)
        while (done < self.length).any():
            rows = np.flatnonzero(done < self.length)
            take = np.minimum(self.length[rows] - done[rows], chunk)
            obs = np.zeros((len(rows), chunk), np.int32)
            for i, (r, n) in enumerate(zip(rows, take)):
                obs[i, :n] = self.prompts[r][done[r]:done[r] + n]
            out = self.server.query_batch({
                "obs": obs, "n_valid": take.astype(np.int32),
                "slot": self.slots[rows],
                "fresh": (done[rows] == 0).astype(np.int32),
                "max_len": self.limit[rows].astype(np.int32)},
                len(rows), timeout=600.0)
            self.tokens_sent += int(take.sum())
            done[rows] += take
            # the first decode token follows the prompt's last by the rule
            for i, r in enumerate(rows):
                if done[r] == self.length[r]:
                    self.newest[r] = self._next(out["q"][i:i + 1],
                                                self.prompts[r][-1:])[0]

    def _next(self, q: np.ndarray, obs: np.ndarray) -> np.ndarray:
        """q [rows, A], obs [rows] -> each row's next token."""
        return ((np.argmax(q, axis=1) + obs.astype(np.int64) * 31 + 7)
                % self.vocab).astype(np.int32)

    def run(self) -> None:
        try:
            self._prefill()
            self.prefilled.set()
            self.go.wait()
            annotate = jax.profiler.TraceAnnotation
            k = len(self.prompts)
            zeros = np.zeros(k, np.int32)
            while not self.stop.is_set():
                if (self.length >= self.limit).any():
                    raise RuntimeError("a session reached its declared "
                                       "length inside the run")
                t0 = time.monotonic()
                try:
                    with annotate("bench.client_query"):
                        out = self.server.query_batch(
                            {"obs": self.newest, "slot": self.slots,
                             "fresh": zeros}, k, timeout=self.timeout)
                except Exception:  # noqa: BLE001 - counted, run goes on
                    self.failures += 1
                    out = None
                t1 = time.monotonic()
                self.queries += 1
                self.latencies_ms.append((t1, (t1 - t0) * 1e3))
                if out is None:
                    continue
                self.tokens_sent += k
                q = out["q"]
                for r, slot in self.checked.items():
                    self.decoded[slot].append(self.newest[r])
                    self.recent[slot].append(
                        (t1, int(self.length[r]), np.array(q[r])))
                self.newest = self._next(q, self.newest)
                self.length += 1
        except Exception as e:  # noqa: BLE001 - reported by run()
            self.error = e
            self.prefilled.set()


def run(rt) -> dict:
    cfg = rt.run_config()
    p = rt.params
    if rt.trace:
        os.makedirs(rt.trace_dir, exist_ok=True)
        cfg = apply_overrides(cfg, [
            "obs.enabled=true", "obs.blackbox=false",
            "obs.trace_path=" + os.path.join(rt.trace_dir,
                                             "program_spans.json")])
    probe = make_env(cfg.env, seed=cfg.seed)
    net = build_network(cfg.network, probe.spec)
    family = fam.family_of(cfg)
    if not fam.keeps_slots(net):
        raise RuntimeError(f"{cfg.network.kind} is not served from slots")
    block = net.slot_block
    slots, max_len, pool_tokens = fam.slot_geometry(cfg, block)
    count = int(p["clients"]) * int(p["sessions_per_client"])
    if count > slots or int(p["start_max"]) + int(p["decode_max"]) > max_len:
        raise RuntimeError(f"the mix asks for {count} sessions of up to "
                           f"{p['start_max']} + {p['decode_max']} positions; "
                           f"the configuration has {slots} of {max_len}")
    prompts = draw_sessions(rt.seed, p, net.num_actions, block, pool_tokens)
    starts = np.asarray([len(x) for x in prompts])
    say(f"{count} sessions, starts {starts.min()}..{starts.max()} "
        f"(mean {starts.mean():.0f}), {int(starts.sum())} prompt tokens")

    params = _bfloat16_params(net, component_key(cfg.seed, "net_init"))
    obs = build_obs(cfg.obs, Metrics())
    server = BatchedInferenceServer(
        fam.server_apply_fn(family, net, cfg), params,
        max_batch=cfg.inference.max_batch,
        deadline_ms=cfg.inference.deadline_ms,
        obs=obs if obs.enabled else None, **fam.server_slots(cfg, net))
    server.update_params(params, 1)
    t_warm = time.monotonic()
    server.warmup(fam.warmup_example(family, cfg, probe.spec))
    say(f"server warm ({time.monotonic() - t_warm:.1f}s): buckets "
        f"{server.warm_buckets}")

    per = int(p["sessions_per_client"])
    checked = checked_slots(starts, p["checked_quantiles"])
    clients = [Client(i, server, prompts[i * per:(i + 1) * per], p,
                      cfg.inference.prefill_chunk, net.num_actions, checked)
               for i in range(int(p["clients"]))]
    t_fill = time.monotonic()
    for c in clients:
        c.start()
    for c in clients:
        c.prefilled.wait()
        if c.error is not None:
            raise RuntimeError(f"prefill failed: {c.error!r}") from c.error
    prefill_s = time.monotonic() - t_fill
    say(f"prefilled {int(starts.sum())} tokens in {prefill_s:.2f}s "
        f"({starts.sum() / prefill_s:.0f} tokens/s)")
    for c in clients:
        c.go.set()
    time.sleep(float(p["settle_s"]))
    fence = jax.jit(lambda x: x + 1)
    fence(np.float32(0)).block_until_ready()
    rt.setup_done()

    def snapshot() -> dict:
        s = server.stats
        return {"t": time.monotonic(), "batches": s["batches"],
                "items": s["items"],
                "failures": sum(c.failures for c in clients),
                "counters": dict(server.slot_counters),
                "spans": obs.tracer.aggregates()}

    with rt.window():
        snap0 = snapshot()
        time.sleep(rt.seconds)
        snap1 = snapshot()
        # stopped HERE, not behind the window's exit: a traced run joins
        # its profiler there, seconds in which the loops would run on
        # past the steps `correct` reads
        for c in clients:
            c.stop.set()
    window_s = snap1["t"] - snap0["t"]
    for c in clients:
        c.join(timeout=60.0)
    errors = [repr(c.error) for c in clients if c.error is not None]
    server.stop()
    lengths = np.asarray(net.slot_lengths(server.slot_state))[:count]
    sent_by_slot = np.concatenate([c.length for c in clients])
    counters = dict(server.slot_counters)
    tokens_sent = sum(c.tokens_sent for c in clients)
    ledger = server.slot_ledger
    named = {k: importlib.import_module(v)
             for k, v in rt.cell.config["checks"].items()}
    # what the server holds of the checked sessions' recurrence, read
    # before the reference takes the room the sessions held
    held = {slot: named["mapper"].device_state(server.slot_state, slot)
            for c in clients for slot in c.checked.values()}
    server.release_slots()

    lat = np.asarray([ms for c in clients for t, ms in c.latencies_ms
                      if snap0["t"] <= t <= snap1["t"]])
    pct = _percentiles(lat)
    # a query that rode a step more than its round's two: over 1.25 x
    # the median (2.5 steps). Where the 99th percentile sits among
    # them is what spreads it (PERF.md section 6, PR 57)
    late = float(np.mean(lat > 1.25 * pct["p50"])) if lat.size else 0.0
    d = {k: snap1[k] - snap0[k] for k in ("batches", "items", "failures")}
    d["queries"] = int(lat.size)    # the queries the percentiles are over
    window_counters = {k: snap1["counters"].get(k, 0)
                       - snap0["counters"].get(k, 0)
                       for k in snap1["counters"]}
    spans = {}
    for name, a1 in snap1["spans"].items():
        a0 = snap0["spans"].get(name, {"count": 0, "total_s": 0.0})
        spans[name] = {"count": a1["count"] - a0["count"],
                       "total_ms": (a1["total_s"] - a0["total_s"]) * 1e3}
    if rt.trace:
        # the serve thread's spans, mean ms a batch
        say("server spans ms " + repr({
            name: round(v["total_ms"] / v["count"], 3)
            for name, v in sorted(spans.items())
            if name.startswith("server.") and v["count"]}))
    sessions = []
    for c in clients:
        for slot in c.checked.values():
            tokens = np.concatenate([prompts[slot], np.asarray(
                c.decoded[slot], np.int32)])
            # the window's last steps (the loop may finish a step or
            # two past it; were none inside, the newest stand)
            inside = ([(at, q) for t, at, q in c.recent[slot]
                       if t <= snap1["t"]]
                      or [(at, q) for _, at, q in c.recent[slot]])[-c.keep:]
            at = np.asarray([a for a, _ in inside])
            sessions.append({"tokens": tokens, "at": at,
                             "q": np.stack([q for _, q in inside]),
                             "state": held[slot]})
    sessions.sort(key=lambda s: s["tokens"].shape[0])
    say(f"check: {len(sessions)} sessions of "
        f"{[int(s['tokens'].shape[0]) for s in sessions]} positions, "
        f"{[len(s['at']) for s in sessions]} steps compared")
    checks, notes = named["check"].check_sessions(
        named["reference"], named["mapper"], params,
        decoder_block(cfg.network)[1], sessions, sent_by_slot, counters,
        bool(p.get("show_limits", False)))
    checks["no_query_failed"] = not errors and sum(
        c.failures for c in clients) == 0
    checks["extend_tokens_counter_is_what_was_sent"] = (
        counters.get("extend_tokens") == tokens_sent)
    checks["slot_lengths_are_what_was_sent"] = bool(
        (lengths == sent_by_slot).all())
    say("check notes " + repr({**notes, "errors": errors, "ledger": ledger}))
    rows_mean = d["items"] / max(d["batches"], 1)
    say(f"window {window_s:.3f}s: {d['queries']} queries "
        f"({d['failures']} failed), latency {pct}, {100 * late:.3f}% over "
        f"1.25 x p50; {d['items']} tokens "
        f"in {d['batches']} batches ({rows_mean:.1f} rows/batch, "
        f"{d['items'] / window_s:.0f} tokens/s); counters "
        f"{window_counters}")
    return {
        "attempted": int(d["queries"]),
        "failed": int(d["failures"]),
        "checks": checks,
        "end_to_end": {"infer_p99_ms": pct.get("p99")},
        "window_s": window_s,
        "server_window": {"batches": d["batches"], "items": d["items"]},
        "query_latency_ms": pct,
        "late_query_share": late,
        "program_spans": spans,
        "slot_counters": window_counters,
        "prefill": {"tokens": int(starts.sum()), "seconds": prefill_s},
        # what the step's floors are counted from: the mix's lengths as
        # the window found them, never the implementation
        "decode": {"rows_per_step": rows_mean,
                   # each session's context at the window's middle
                   "contexts": (sent_by_slot
                                - d["items"] / count / 2).tolist()},
        "slot_ledger": ledger,
        "family": rt.cell.config["family"],
    }
