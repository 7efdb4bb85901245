"""Remote actor host: actors on another machine feeding a learner host.

The reference scales to 256 actors by spawning actor processes on many
machines, each pushing experience and pulling parameters over gRPC
(SURVEY.md §3.1). The TPU-native equivalent: this module runs N actor
threads on a CPU host, evaluates the policy on a LOCAL batched inference
server (CPU jit — actor hosts have no TPU), pushes experience to the
learner host's SocketIngestServer over DCN, and pulls fresh parameters
on a cadence through the same connection.

Entry points:
- run_actor_host(cfg, host, port, ...) — library call.
- `python -m ape_x_dqn_tpu.runtime.actor_host --config pong
  --connect HOST:PORT --actors 4` — one actor machine.
"""

from __future__ import annotations

import dataclasses
import os
import socket as socket_mod
import threading
import time

from ape_x_dqn_tpu.comm.socket_transport import SocketTransport
from ape_x_dqn_tpu.configs import RunConfig
from ape_x_dqn_tpu.envs import make_env
from ape_x_dqn_tpu.models import build_network
from ape_x_dqn_tpu.obs.core import build_obs
from ape_x_dqn_tpu.obs.fleet import StampingTransport, TelemetryEmitter
from ape_x_dqn_tpu.parallel.inference_server import (
    BatchedInferenceServer, build_serving_tier)
from ape_x_dqn_tpu.runtime.family import (
    actor_class, family_of, server_apply_fn, server_slots, warmup_example)
from ape_x_dqn_tpu.utils.compile_cache import ensure_compile_cache
from ape_x_dqn_tpu.utils.metrics import Metrics, device_stamp


def default_peer_id(actor_offset: int = 0) -> str:
    """Stable-for-the-process, unique-across-the-fleet peer identity:
    hostname + pid + this host's slot in the global actor schedule."""
    return (f"{socket_mod.gethostname()}-{os.getpid()}"
            f"-a{actor_offset}")


def run_actor_host(cfg: RunConfig, host: str, port: int,
                   num_actors: int | None = None,
                   actor_offset: int = 0,
                   frames_per_actor: int | None = None,
                   param_poll_s: float | None = None,
                   stop_event: threading.Event | None = None,
                   wait_for_params_s: float = 60.0,
                   peer_id: str | None = None,
                   supervise: bool = False) -> dict:
    """Run actors against a remote learner until their frame budget ends.

    actor_offset positions this host's actors inside the global eps_i
    schedule (host k of m runs indices [k*n, (k+1)*n) of num_actors*m).

    param_poll_s=None (the default) paces parameter pulls by ENV STEPS:
    the puller refreshes once the host's actors collectively advance
    cfg.actors.param_pull_every frames per actor — Horgan et al. 2018's
    "actors pull every ~400 env steps" — with a 30s keep-alive floor so
    an idle host still tracks the live epoch. Passing a float restores
    the fixed wall-clock cadence (bandwidth-constrained links where
    seconds, not steps, are the budget).

    peer_id names this host on the fleet telemetry plane (obs/fleet.py);
    with obs enabled, experience batches are stamped with it plus a
    monotonic batch_id, and a TelemetryEmitter ships obs snapshot
    frames to the learner every cfg.obs.telemetry_every_s.

    supervise=True makes this host survive learner restarts instead of
    exiting: the bootstrap wait for first params never times out (the
    transport's supervised reconnect loop keeps re-entering connect/
    negotiate under backoff until a learner — the same one or a new
    incarnation at the same address — answers), and mid-run learner
    loss is already survived by the transport (sends drop-and-back-off,
    params re-converge to the live epoch on reconnect).
    """
    n = num_actors or cfg.actors.num_actors
    stop_event = stop_event or threading.Event()
    peer = peer_id or default_peer_id(actor_offset)
    comm = cfg.comm
    serving = cfg.serving
    transport = SocketTransport(
        host, port, wire_codec=comm.wire_codec,
        reconnect_base_s=getattr(comm, "reconnect_base_s", 0.05),
        reconnect_cap_s=getattr(comm, "reconnect_cap_s", 2.0),
        params_push=getattr(comm, "params_push", False),
        param_codec=getattr(comm, "param_codec", "delta-q8"),
        serve_policy=(cfg.env.id if serving.multi_tenant else ""),
        serve_class=serving.default_class,
        shm=getattr(comm, "shm", False),
        shm_slots=getattr(comm, "shm_slots", 8),
        shm_slot_bytes=getattr(comm, "shm_slot_bytes", 1 << 22))
    # the raw socket transport, before any StampingTransport wrap: the
    # serving tier's backpressure callback must reach the object that
    # owns send_experience's drop gate
    raw_transport = transport
    # local obs: metrics stay in-memory (the learner's JSONL is the
    # run's single artifact; this host's view crosses the wire as
    # telemetry frames), and a trace path gets a per-peer suffix so
    # co-located hosts don't clobber the learner's trace file
    obs_cfg = cfg.obs
    if obs_cfg.trace_path:
        obs_cfg = dataclasses.replace(
            obs_cfg, trace_path=f"{obs_cfg.trace_path}.{peer}")
    obs = build_obs(obs_cfg, Metrics())
    # forensics plane: this host's flight recorder dumps under the
    # peer's name, with the transport's reconnect/drop tallies merged
    # into every dump (SIGUSR2 install is skipped off the main thread)
    obs.blackbox.set_peer(peer)
    obs.blackbox.add_context_provider(
        lambda: {"transport": {
            "reconnects": raw_transport.reconnects,
            "dropped": raw_transport.dropped,
            "drop_reasons": dict(raw_transport.drop_reasons),
            "epoch": raw_transport.epoch}})
    obs.blackbox.install()
    emitter: TelemetryEmitter | None = None
    if obs.enabled:
        transport = StampingTransport(transport, peer)
        emitter = TelemetryEmitter(transport, obs, peer,
                                   interval_s=cfg.obs.telemetry_every_s)

    # wait for the learner to publish a first param set; under
    # --supervise the wait is unbounded (a host that outlives its
    # learner must keep re-entering connect until one comes back)
    deadline = time.monotonic() + wait_for_params_s
    params, version = transport.get_params()
    while params is None and not stop_event.is_set() \
            and (supervise or time.monotonic() < deadline):
        time.sleep(0.2)
        params, version = transport.get_params()
    if params is None:
        transport.close()
        raise TimeoutError("learner never published parameters")

    probe = make_env(cfg.env, seed=cfg.seed)
    net = build_network(cfg.network, probe.spec)
    # family dispatch shared with the driver (runtime/family.py): the
    # server protocol, actor class, and warmup example must all match
    # what the learner host's published params expect
    family = family_of(cfg)
    if serving.multi_tenant:
        # multi-tenant serving tier: this host's policy registers under
        # env.id; the tier's admission controller pushes backpressure
        # into the transport's drop gate when the queue crosses the SLO
        tier = build_serving_tier(
            serving, max_batch=cfg.inference.max_batch,
            deadline_ms=cfg.inference.deadline_ms,
            obs=obs if obs.enabled else None)
        if serving.backpressure:
            tier.on_backpressure = raw_transport.set_backpressure
        server = tier.register_policy(
            cfg.env.id, server_apply_fn(family, net, cfg), params,
            family=family, priority=serving.default_class)
    else:
        server = BatchedInferenceServer(
            server_apply_fn(family, net, cfg), params,
            max_batch=cfg.inference.max_batch,
            deadline_ms=cfg.inference.deadline_ms,
            obs=obs if obs.enabled else None, **server_slots(cfg, net))
    server.update_params(params, version)
    if emitter is not None:
        emitter.start()
    # pre-compile the forward so first queries don't time out
    server.warmup(warmup_example(family, cfg, probe.spec),
                  extra_sizes=(cfg.actors.envs_per_actor,))

    # step-paced pulls (param_poll_s=None) read the live actors' frame
    # counters: refresh once the fleet advances param_pull_every frames
    # per actor. The counters are plain ints bumped by the actor
    # threads — a cadence heuristic, racy reads are fine.
    live_actors: list = [None] * n
    frame_paced = param_poll_s is None
    poll_tick = 0.2 if frame_paced else param_poll_s
    pull_every_frames = max(cfg.actors.param_pull_every, 1) * n

    def param_puller() -> None:
        # resilience contract: NOTHING in here may kill the thread — a
        # transient pull failure keeps last-good params on the server,
        # bumps the param_pull_errors counter, and widens the poll wait
        # (bounded backoff) until pulls succeed again. An epoch change
        # (learner restart) FORCES the update even when the new
        # incarnation's version counter restarted below ours — version
        # monotonicity only holds within one epoch.
        seen_epoch = transport.param_epoch
        seen_pull_errors = transport.param_pull_errors
        fail_streak = 0
        pulled_at_frames = 0
        pulled_at_t = time.monotonic()
        while not stop_event.wait(
                min(poll_tick * (2 ** min(fail_streak, 4)), 30.0)):
            if frame_paced and fail_streak == 0:
                total = sum(a.frames for a in live_actors
                            if a is not None)
                if (total - pulled_at_frames < pull_every_frames
                        and time.monotonic() - pulled_at_t < 30.0):
                    continue
                pulled_at_frames = total
            pulled_at_t = time.monotonic()
            try:
                # server-pushed params (if negotiated) take priority —
                # they are publish-fresh; the conditional poll is the
                # fallback and the keep-alive
                p, v = transport.poll_pushed_params()
                if p is None:
                    p, v = transport.get_params()
                errs = transport.param_pull_errors
                if errs > seen_pull_errors:
                    obs.count("param_pull_errors", errs - seen_pull_errors)
                    seen_pull_errors = errs
                    fail_streak += 1
                    continue
                fail_streak = 0
                if p is None:  # "unchanged" reply or nothing pushed
                    continue
                ep = transport.param_epoch
                if v > server.params_version \
                        or (ep != -1 and ep != seen_epoch):
                    server.update_params(p, v)
                seen_epoch = ep
            except Exception:  # noqa: BLE001 - puller must outlive anything
                obs.count("param_pull_errors")
                fail_streak += 1

    puller = threading.Thread(target=param_puller, name="param-pull",
                              daemon=True)
    puller.start()

    # remediation plane, host side (runtime/remediation.py): the
    # learner-side engine cannot reach this host's transport latch, so
    # an ENFORCE-mode host runs a stale-latch watchdog of its own — a
    # transport backpressure latch that the local admission controller
    # DISAGREES with (tier released or never engaged, latch still set)
    # for remediation.release_after_s is released locally. Complements
    # the epoch-change clear in comm/socket_transport._note_epoch:
    # that one needs a reply from the new incarnation to arrive; this
    # one covers a latch desynced by a controller that went silent.
    rcfg = getattr(cfg, "remediation", None)
    bp_thread: threading.Thread | None = None
    if (rcfg is not None and rcfg.mode == "enforce"
            and serving.multi_tenant and serving.backpressure):
        def bp_watchdog() -> None:
            stale_since: float | None = None
            while not stop_event.wait(1.0):
                stale = (raw_transport.backpressure_engaged
                         and not tier.backpressure_engaged)
                if not stale:
                    stale_since = None
                    continue
                now = time.monotonic()
                if stale_since is None:
                    stale_since = now
                elif now - stale_since >= rcfg.release_after_s:
                    raw_transport.set_backpressure(False)
                    obs.count("remediation_actions")
                    stale_since = None

        bp_thread = threading.Thread(target=bp_watchdog,
                                     name="remediation-bp", daemon=True)
        bp_thread.start()

    per_actor = frames_per_actor or (
        cfg.total_env_frames // max(cfg.actors.num_actors, 1))
    errors: list[tuple[int, Exception]] = []
    frames = [0] * n

    cls = actor_class(family)

    def actor_thread(slot: int) -> None:
        idx = actor_offset + slot
        try:
            actor = cls(cfg, idx, server.query_batch, transport,
                        obs=obs if obs.enabled else None)
            live_actors[slot] = actor  # puller paces pulls off .frames
            frames[slot] = actor.run(per_actor, stop_event)
            obs.clear(f"actor-{idx}")  # finished, not stalled
        except Exception as e:  # noqa: BLE001 - reported to caller
            # the thread dies quietly from the interpreter's point of
            # view (no excepthook) — archive the ring ourselves
            obs.blackbox.record("actor_error", component=f"actor-{idx}",
                                error=repr(e)[:200])
            obs.blackbox.dump("actor_error", component=f"actor-{idx}")
            errors.append((idx, e))

    threads = [threading.Thread(target=actor_thread, args=(i,),
                                name=f"actor-{actor_offset + i}",
                                daemon=True)
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        # bounded join in a liveness loop: actors run to frame budget,
        # but a wedged worker must not wedge teardown unobservably
        while t.is_alive():
            t.join(timeout=5.0)
    stop_event.set()
    puller.join(timeout=2)
    if bp_thread is not None:
        bp_thread.join(timeout=2)
    server.stop()
    if emitter is not None:
        emitter.stop()  # ships one shutdown-fresh frame
    obs.close()
    transport.close()
    return {"frames": sum(frames), "actors": n,
            "dropped": transport.dropped, "errors": errors,
            "drop_reasons": transport.drop_reasons,
            "reconnects": transport.reconnects,
            "epoch": transport.epoch,
            "epoch_changes": transport.epoch_changes,
            "param_pull_errors": transport.param_pull_errors,
            "param_pushes_in": transport.param_pushes_in,
            "param_codec_negotiated": transport.param_codec_negotiated,
            "param_resyncs": transport.param_resyncs,
            "bytes_out": transport.bytes_out,
            "wire_codec": transport.negotiated_codec,
            "wire_compression_ratio": round(
                transport.wire_compression_ratio, 3),
            "encode_ms": round(transport.encode_ms, 1),
            "param_bytes_in": transport.bytes_in,
            "last_param_version": server.params_version,
            "peer_id": peer,
            "telemetry_negotiated": transport.telemetry_negotiated,
            "serve_negotiated": raw_transport.serve_negotiated,
            "shm_negotiated": raw_transport.shm_negotiated,
            "shm_posts": raw_transport.shm_posts,
            "shm_fallbacks": raw_transport.shm_fallbacks,
            "shm_bytes_out": raw_transport.shm_bytes_out,
            "shm_param_reads": raw_transport.shm_param_reads,
            "telemetry_frames_out": transport.telemetry_frames_out}


def main(argv: list[str] | None = None) -> int:
    import argparse

    import jax

    # actor hosts evaluate the policy on THEIR cpu (no TPU in the
    # reference's actor machines either). Selected here, before any
    # backend use and whatever the environment says: a chip belongs to
    # one process, and a co-located actor host that took the learner's
    # would leave one of the two hung
    jax.config.update("jax_platforms", "cpu")
    ensure_compile_cache()

    from ape_x_dqn_tpu.configs import get_config
    from ape_x_dqn_tpu.runtime.train import apply_overrides

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", required=True)
    ap.add_argument("--connect", required=True, metavar="HOST:PORT")
    ap.add_argument("--actors", type=int, default=None)
    ap.add_argument("--actor-offset", type=int, default=0)
    ap.add_argument("--frames-per-actor", type=int, default=None)
    ap.add_argument("--param-poll-s", type=float, default=None,
                    help="fixed seconds between parameter pulls from "
                         "the learner. Default: step-paced — pull once "
                         "this host's actors advance "
                         "actors.param_pull_every env steps each "
                         "(Ape-X's ~400), 30s keep-alive. Each pull "
                         "moves the full param tree over DCN, so on "
                         "bandwidth-constrained links set the seconds "
                         "toward the staleness you can tolerate")
    ap.add_argument("--peer-id", default=None,
                    help="name of this host on the fleet telemetry "
                         "plane (default: hostname-pid-a<offset>); "
                         "shows up as peer/<id>/ in the learner's "
                         "report and in stall attributions")
    ap.add_argument("--supervise", action="store_true",
                    help="survive learner restarts: wait indefinitely "
                         "for first params and keep re-entering the "
                         "connect/negotiate path (backoff-capped) when "
                         "the learner goes away mid-run, instead of "
                         "exiting — the elastic-fleet mode for hosts "
                         "managed by a process supervisor")
    ap.add_argument("--set", action="append", default=[],
                    metavar="dotted.key=value")
    args = ap.parse_args(argv)
    cfg = apply_overrides(get_config(args.config), args.set)
    host, port = args.connect.rsplit(":", 1)
    out = run_actor_host(cfg, host, int(port), num_actors=args.actors,
                         actor_offset=args.actor_offset,
                         frames_per_actor=args.frames_per_actor,
                         param_poll_s=args.param_poll_s,
                         peer_id=args.peer_id,
                         supervise=args.supervise)
    # the device stamp shows this host stayed off the learner's chip
    print({**out, **device_stamp()})
    return 1 if out["errors"] else 0


if __name__ == "__main__":
    raise SystemExit(main())
