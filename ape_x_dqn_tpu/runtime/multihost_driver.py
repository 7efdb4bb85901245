"""Multi-host Ape-X: one learner process per host, SPMD lockstep.

The reference's multi-host learner is NCCL/MPI process groups running
synchronized training steps while each host ingests its own actors'
experience (SURVEY.md §5 "distributed communication backend"). The
TPU-native shape of that design:

- Every process builds the SAME global (dp, tp) mesh (parallel/mesh.py
  over jax.devices(), which spans hosts under jax.distributed) and the
  same DistLearner; GSPMD inserts the cross-host collectives.
- Each host runs its OWN actors + batched inference server + transport;
  experience lands only in the dp replay rows that host owns
  (parallel/multihost.process_rows) — experience never crosses hosts,
  exactly like the reference's per-learner replay locality.
- The learner loop is a synchronous ROUND protocol instead of the
  single-host driver's free-running threads: jitted programs on global
  arrays are collectives, so every process must issue the identical
  call sequence. Each round:

      1. all processes agree (the packed global_stats reduction)
         whether every host has a full ingest block staged; if so, all
         call `add` together — gating beats padding, because dead
         filler items would cycle the replay ring and evict real
         experience on idle hosts;
      2. the replay fill check, train_many dispatch, publication
         boundary, and termination all branch on GLOBAL values (jit
         outputs or the global_stats reduction), never on host-local
         state.

  A host whose actors all die stalls global ingest (training continues
  on existing data); a host whose PROCESS dies hangs the collectives —
  the same failure domain as the reference's NCCL group, recovered by
  restarting the job from a checkpoint.

Run via the CLI:
    python -m ape_x_dqn_tpu.runtime.train --config pong \
        --coordinator HOST:PORT --num-processes 2 --process-id 0 ...
"""

from __future__ import annotations

import dataclasses
import sys
import threading
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ape_x_dqn_tpu.comm.transport import LoopbackTransport
from ape_x_dqn_tpu.configs import RunConfig
# StallWatchdog moved to the observability layer (obs/health.py) so the
# single-host heartbeat watchdog and this lockstep watchdog live
# together; re-exported here because tests and operational docs import
# it from this module.
from ape_x_dqn_tpu.obs.health import StallWatchdog, make_lock  # noqa: F401
from ape_x_dqn_tpu.obs.core import build_obs
from ape_x_dqn_tpu.envs import make_env
from ape_x_dqn_tpu.models import build_network
from ape_x_dqn_tpu.parallel.inference_server import (
    BatchedInferenceServer, build_serving_tier)
from ape_x_dqn_tpu.parallel.mesh import make_mesh
from ape_x_dqn_tpu.parallel import multihost
from ape_x_dqn_tpu.runtime.driver import build_prioritized_replay
from ape_x_dqn_tpu.runtime.evaluation import (
    EvalWorker, make_eval_policy_factory)
from ape_x_dqn_tpu.runtime.family import (
    actor_class, build_learner, family_of, family_setup, hbm_price,
    server_apply_fn, server_slots, warmup_example)
from ape_x_dqn_tpu.utils.checkpoint import CheckpointManager
from ape_x_dqn_tpu.utils.hbm import check_hbm_fits
from ape_x_dqn_tpu.utils.metrics import Metrics, log_run_header
from ape_x_dqn_tpu.utils.misc import next_pow2
from ape_x_dqn_tpu.utils.rng import component_key




class MultihostApexDriver:
    """Synchronous-round Ape-X driver; one instance per learner process.

    Supports the flat-DQN family (both storage layouts) and the
    recurrent R2D2 family (stored-state sequence replay, both item
    layouts). The continuous DPG family runs multi-host today by
    putting its ACTORS on remote hosts (runtime/actor_host.py) against
    a single-process learner — its nets are small enough that a
    sharded learner buys nothing (see ApexDriver's matching gate).
    """

    def __init__(self, cfg: RunConfig, metrics: Metrics | None = None,
                 transport=None):
        if cfg.checkpoint_replay:
            # loud, not a silent no-op: the multihost payload gather is
            # a replicated-host collective, and replicating every dp
            # shard's replay to every host would multiply the payload
            # by dp x capacity — needs a sharded save path first
            raise NotImplementedError(
                "checkpoint_replay is single-host only for now "
                "(ApexDriver); the multihost driver checkpoints "
                "params/opt/rng/step/frames and refills replay on "
                "resume — set checkpoint_replay=False here")
        # a 1-process fleet is valid ONLY under an initialized
        # jax.distributed runtime (the CLI's --coordinator path; the
        # driver artifact certifies the round protocol that way) —
        # plain single-process training belongs in ApexDriver
        dist_on = jax.distributed.is_initialized()
        assert jax.process_count() > 1 or dist_on, \
            "MultihostApexDriver requires jax.distributed (use ApexDriver " \
            "for single-process runs)"
        self.cfg = cfg
        self.family = family_of(cfg)
        if self.family == "dpg":
            raise NotImplementedError(
                "the multihost lockstep loop covers the DQN and R2D2 "
                "families; DPG nets are small — run the learner "
                "single-process with remote actor hosts "
                "(runtime/actor_host.py)")
        self.metrics = metrics or Metrics()
        # observability facade (obs/): spans around the collective
        # round stages + per-publish instrument snapshots; NULL_OBS
        # unless cfg.obs.enabled. The round-progress StallWatchdog
        # below is collective-aware and stays the stall authority here.
        self.obs = build_obs(getattr(cfg, "obs", None), self.metrics)
        probe_env = make_env(cfg.env, seed=cfg.seed)
        self.spec = probe_env.spec
        self.net = build_network(cfg.network, self.spec)
        obs0 = probe_env.reset()

        self.mesh = make_mesh(dp=cfg.parallel.dp, tp=cfg.parallel.tp)
        self.row_start, self.row_stop = multihost.process_rows(self.mesh)
        self.dp = cfg.parallel.dp
        self.dp_local = self.row_stop - self.row_start

        # family_setup (runtime/family.py) owns params init + replay
        # item layout + staging geometry, shared with ApexDriver
        setup = family_setup(cfg, self.spec, self.net, obs0)
        params, item_spec = setup.params, setup.item_spec
        self._frame_mode = setup.frame_mode
        self._chunk = setup.stage_chunk
        self._item_keys = tuple(item_spec.keys())
        self._item_spec = item_spec
        if cfg.replay.kind not in ("prioritized", "sequence"):
            # ValueError, not assert: user-config validation must
            # survive `python -O` (neighboring checkpoint_dir check
            # raises too) — an invalid kind would otherwise surface as
            # an opaque failure inside the dist learner
            raise ValueError(
                "the multihost learner requires prioritized replay "
                "(the per-shard sum-trees ARE the sharded state; "
                "kind='sequence' for R2D2); got "
                f"replay.kind={cfg.replay.kind!r}")

        # early, loud HBM fits-check (utils/hbm.py): the per-shard
        # replay + replicated model state must fit each chip before any
        # device allocation happens
        check_hbm_fits(
            cfg, self.spec.obs_shape, self.spec.obs_dtype,
            param_count=sum(int(np.prod(l.shape))
                            for l in jax.tree.leaves(params)),
            **hbm_price(cfg, self.net))

        # identical construction on every process (same cfg.seed) ->
        # identical initial params; learner.init then shards them over
        # the global mesh (a collective: all processes reach this line)
        shard_cap = next_pow2(max(cfg.replay.capacity // self.dp, 2))
        self.replay = build_prioritized_replay(cfg, self.spec, shard_cap,
                                               self._frame_mode)
        self.capacity = shard_cap * self.dp
        self.learner = build_learner(cfg, self.net, self.replay, self.mesh)
        self.state = self.learner.init(
            params, item_spec, component_key(cfg.seed, "learner"))

        # publication is a global collective (tp all-gather + cross-host
        # replication); the inference server's jit runs process-LOCALLY,
        # so it gets a host copy — a global array would not mix with the
        # server's local inputs. With shard_over_mesh the server spreads
        # query batches over THIS process's devices (a process-local
        # mesh: only addressable devices, so its jit stays collective-
        # free and cannot perturb the global lockstep).
        local = jax.local_devices()
        self._inference_mesh = (
            make_mesh(dp=len(local), tp=1, devices=local)
            if cfg.inference.shard_over_mesh and len(local) > 1 else None)
        server_params = self._host_params()
        # the serving tier stays process-local for the same reason the
        # inference mesh does: admission/dispatch never cross hosts, so
        # multi-tenancy cannot perturb the global lockstep
        self.serving = None
        if cfg.serving.multi_tenant:
            self.serving = build_serving_tier(
                cfg.serving,
                max_batch=cfg.inference.max_batch,
                deadline_ms=cfg.inference.deadline_ms,
                mesh=self._inference_mesh, obs=self.obs)
            self.server = self.serving.register_policy(
                cfg.env.id, server_apply_fn(self.family, self.net, cfg),
                server_params, family=self.family,
                priority=cfg.serving.default_class)
        else:
            self.server = BatchedInferenceServer(
                server_apply_fn(self.family, self.net, cfg), server_params,
                max_batch=cfg.inference.max_batch,
                deadline_ms=cfg.inference.deadline_ms,
                mesh=self._inference_mesh, obs=self.obs,
                **server_slots(cfg, self.net))
        self.transport = transport if transport is not None \
            else LoopbackTransport()
        # fleet telemetry (obs/fleet.py): merge remote actor hosts'
        # snapshot frames into this process's JSONL — purely host-local
        # (no collectives), so it cannot perturb the lockstep rounds
        self.fleet = None
        if self.obs.enabled:
            from ape_x_dqn_tpu.obs.fleet import FleetAggregator

            agg = FleetAggregator(self.obs)
            if agg.install(self.transport):
                self.fleet = agg
        self.transport.publish_params(server_params, 0)

        self.stop_event = threading.Event()
        self.episode_returns: deque[float] = deque(maxlen=200)  # guarded-by: _lock
        self._frames_local = 0  # guarded-by: _lock
        # frame counters survive resume: _frames_base restores from the
        # checkpoint so a --total-env-frames budget CONTINUES after a
        # preemption instead of re-running in full (round-2 advisor
        # finding); _frames_global_latest mirrors the last packed
        # collective's total (identical on every process) for the
        # checkpoint payload
        self._frames_base = 0
        self._frames_global_latest = 0
        self._grad_steps = 0
        self._gather_jit = None
        self._restored_step: int | None = None
        # checkpoint/resume (SURVEY.md §5): the gather to host is a
        # collective every process joins, and every process calls the
        # (internally synchronized) orbax manager; the bytes land once
        # via the primary process, so checkpoint_dir should be a SHARED
        # filesystem for restore to reach every process (a host whose
        # dir is empty makes the fleet agree on "no restore" rather
        # than hang — see _maybe_restore)
        # all-or-none agreement BEFORE the orbax manager exists: its
        # CONSTRUCTOR already runs multiprocess collectives, so a fleet
        # where only some processes got --checkpoint-dir would issue
        # mismatched collective programs (orbax allgather on some
        # hosts, this min on others) and die in a Gloo timeout with an
        # inscrutable error; every process can see the disagreement
        # here and error loudly instead
        has = 1 if cfg.checkpoint_dir else 0
        mn = multihost.global_min_scalar(self.mesh, has)
        mx = -multihost.global_min_scalar(self.mesh, -has)
        if mn != mx:
            raise ValueError(
                "checkpoint_dir must be set on EVERY process or none "
                f"(this process: {'set' if has else 'unset'}) — "
                "checkpoint save/restore are collectives")
        self.ckpt = (CheckpointManager(cfg.checkpoint_dir)
                     if cfg.checkpoint_dir else None)
        if self.ckpt is not None:
            self._maybe_restore()
        self._stage: list[dict] = []
        self._stage_n = 0
        self._actor_threads: list[threading.Thread] = []
        self._saw_remote = False  # first remote actor-host connection
        self._lock = make_lock("multihost_driver._lock")
        self.actor_errors: list[tuple[int, Exception]] = []  # guarded-by: _lock
        self.last_eval: dict | None = None  # guarded-by: _lock
        self._eval_error: Exception | None = None  # guarded-by: _lock

    # -- checkpoint/resume -------------------------------------------------

    def _ckpt_payload(self) -> dict:
        """COLLECTIVE: TrainState minus replay, gathered to fully
        replicated host numpy — every process must call this at the
        same point; the result is identical everywhere. PRNG keys ride
        as raw key data (numpy can't hold typed keys)."""
        if self._gather_jit is None:
            repl = NamedSharding(self.mesh, P())
            self._gather_jit = jax.jit(
                lambda p, t, o, r, s: (p, t, o, jax.random.key_data(r),
                                       s),
                out_shardings=repl)
        s = self.state
        p, t, o, r, step = self._gather_jit(
            s.params, s.target_params, s.opt_state, s.rng, s.step)
        out = jax.tree.map(np.asarray, {
            "params": p, "target_params": t, "opt_state": o,
            "rng": r, "step": step})
        # host scalar, identical everywhere (it is the last packed
        # collective's output): lets a frame-budget run resume its
        # budget instead of restarting it
        out["frames_global"] = np.asarray(self._frames_global_latest,
                                          np.int64)
        return out

    def _save_checkpoint(self, wait: bool = False) -> None:
        # EVERY process calls save: orbax's multiprocess manager
        # synchronizes internally (barriers inside save/close), so a
        # process-0-only call would deadlock the others; the payload is
        # replicated host numpy, which orbax writes once from the
        # primary process
        with self.obs.span("ckpt.save", step=self._grad_steps):
            payload = self._ckpt_payload()  # collective: all processes
            self.ckpt.save(self._grad_steps, payload, wait=wait)

    def _restore_leaf(self, x, ref):
        """Host numpy -> global array with ref's sharding (the callback
        hands each process the slices it owns; every process holds the
        identical full host copy).

        Only a NamedSharding on the global mesh is trusted: scalar jit
        outputs (optimizer counters, step) can surface with a
        SingleDeviceSharding, which names a DIFFERENT device on each
        process — rebuilding with it would give every host its own
        incompatible copy and the next collective jit rejects the
        state. Those leaves restore replicated on the mesh instead."""
        x = np.asarray(x)
        sharding = (ref.sharding
                    if isinstance(ref.sharding, NamedSharding)
                    else NamedSharding(self.mesh, P()))
        if jnp.issubdtype(ref.dtype, jax.dtypes.prng_key):
            data = jax.make_array_from_callback(
                x.shape, NamedSharding(self.mesh, P("dp")),
                lambda idx: x[idx])
            return jax.jit(jax.random.wrap_key_data,
                           out_shardings=sharding)(data)
        return jax.make_array_from_callback(x.shape, sharding,
                                            lambda idx: x[idx])

    def _maybe_restore(self) -> None:
        """Restore the newest checkpoint step EVERY process can read.
        The min-agreement makes a missing/stale directory on one host
        degrade to a fresh start (or an older common step) instead of
        deadlocking the collectives."""
        local = self.ckpt.latest_step()
        agreed = multihost.global_min_scalar(
            self.mesh, -1 if local is None else int(local))
        if agreed < 0:
            return
        # template restore (the fresh state's own payload): a raw
        # restore would hand back plain dicts/lists where the live
        # opt_state is an optax NamedTuple chain, and the re-shard
        # tree.map would see mismatched structures
        raw = self.ckpt.restore(agreed, template=self._ckpt_payload())
        put = {
            k: jax.tree.map(self._restore_leaf, v,
                            getattr(self.state, k))
            for k, v in raw.items()
            if k not in ("step", "frames_global")}
        step = jax.make_array_from_callback(
            (), NamedSharding(self.mesh, P()),
            lambda idx: np.asarray(raw["step"], np.int32))
        self.state = self.state._replace(step=step, **put)
        self._grad_steps = int(raw["step"])
        self._frames_base = int(raw.get("frames_global", 0))
        self._frames_global_latest = self._frames_base
        self._restored_step = agreed
        # republish: the inference server and transport were seeded
        # with the FRESH init params at construction; without this,
        # resumed actors refill the empty replay with a random policy
        # until the first publish_every boundary (the single-host
        # _maybe_restore ends with _publish_params for the same reason)
        pub = self._host_params()
        self.server.update_params(pub, self._grad_steps)
        self.transport.publish_params(pub, self._grad_steps)

    def _host_params(self):
        """publish_params (collective, all processes call) -> host numpy
        (valid per-process because the result is fully replicated). In
        sharded-inference mode the copy lands back on the local mesh
        (replicated) so the server does not re-upload params from host
        memory on every batch dispatch."""
        pub = self.learner.publish_params(self.state)
        host = jax.tree.map(np.asarray, pub)
        if self._inference_mesh is not None:
            host = jax.device_put(
                host, NamedSharding(self._inference_mesh, P()))
        return host

    # -- local actor plumbing (per host) ----------------------------------

    def _on_episode(self, actor_index: int, info: dict) -> None:
        with self._lock:
            self.episode_returns.append(float(info["episode_return"]))

    def _actor_thread(self, i: int, max_frames: int) -> None:
        try:
            # distinct global actor identities per host: without the
            # process offset every host's actor i would share seeds,
            # eps_i, and (lockstep-identical) params — N hosts producing
            # byte-identical trajectories has the data diversity of one.
            # The eps_i schedule spans the num_actors * nproc fleet, the
            # same convention as actor_host.py --actor-offset.
            n_local = self.cfg.actors.num_actors
            acfg = dataclasses.replace(
                self.cfg, actors=dataclasses.replace(
                    self.cfg.actors,
                    num_actors=n_local * jax.process_count()))
            # actors compute their per-env eps slots from acfg's global
            # num_actors, so the schedule spans the whole
            # nproc * num_actors * envs_per_actor fleet
            actor = actor_class(self.family)(
                acfg, jax.process_index() * n_local + i,
                self.server.query_batch, self.transport,
                episode_callback=self._on_episode, obs=self.obs)
            actor.run(max_frames, self.stop_event)
        except Exception as e:  # noqa: BLE001 - reported in run() output
            with self._lock:
                self.actor_errors.append((i, e))

    def _make_eval_worker(self, game: str | None = None) -> EvalWorker:
        factory = make_eval_policy_factory(
            self.family, self.cfg, self.server.query)
        return EvalWorker(self.cfg, self.server.query, game=game,
                          policy_factory=factory)

    def _eval_loop(self) -> None:
        """Greedy eval on PROCESS 0 only, between publish boundaries
        (SURVEY.md §2.2 'Eval worker'; round-2 verdict missing #3: the
        flagship topology could not measure its north-star metric
        during training). Collective-free by construction: the worker
        builds its own host-local env and queries the process-local
        inference server jit, so it can run concurrently with the
        lockstep round loop without perturbing any process's collective
        call sequence — the other processes neither know nor care."""
        try:
            from ape_x_dqn_tpu.runtime.evaluation import (
                RollingSuiteScore, eval_game_rotation, run_eval_measured)
            every = self.cfg.eval_every_steps
            rotate, games = eval_game_rotation(self.cfg)
            worker = None if rotate else self._make_eval_worker()
            rolling = RollingSuiteScore(self.cfg) if rotate else None
            next_at = every
            eval_i = 0
            while not self.stop_event.wait(0.2):
                if self._grad_steps < next_at:
                    continue
                game = None
                if rotate:
                    game = games[eval_i % len(games)]
                    worker = self._make_eval_worker(game=game)
                    eval_i += 1
                t_eval = time.monotonic()
                try:
                    res, depth_max = run_eval_measured(
                        worker, self.cfg.eval_episodes, self.server,
                        stop_event=self.stop_event,
                        max_frames=self.cfg.eval_max_frames)
                except TimeoutError as e:
                    # transient server stall: skip this rotation slot,
                    # keep the eval thread alive (same guard as
                    # ApexDriver._eval_loop — the round-5 live rotation
                    # died 14 games in on one stalled query)
                    self.metrics.log(self._grad_steps,
                                     eval_game=game or self.cfg.env.id,
                                     eval_error=repr(e))
                    next_at = (self._grad_steps // every + 1) * every
                    continue
                if res is None:  # cancelled mid-eval at shutdown
                    break
                with self._lock:
                    self.last_eval = res
                # max queue depth DURING the eval = the back-pressure it
                # induced (round-3 advisor: post-eval snapshots read ~0);
                # rolling suite table per round-3 weak #7
                roll = (rolling.update(game, res["mean_return"])
                        if rolling is not None and game else {})
                self.metrics.log(self._grad_steps,
                                 avg_eval_return=res["mean_return"],
                                 eval_episodes=res["episodes"],
                                 eval_game=game or self.cfg.env.id,
                                 eval_wall_s=time.monotonic() - t_eval,
                                 server_queue_depth_max=depth_max,
                                 **roll)
                next_at = (self._grad_steps // every + 1) * every
        except Exception as e:  # noqa: BLE001 - surfaced in run() output
            with self._lock:
                self._eval_error = e

    def _pump_ingest(self) -> None:
        """Drain the transport into the local stage (runs each round —
        no separate ingest thread: the round loop owns the state).

        While producers are live the stage is capped at a few ingest
        blocks: the round loop consumes at most one block per round, so
        an uncapped pump would absorb everything actors produce during
        train_many (unbounded host memory) and defeat the transport's
        drop-oldest backpressure, which is where overflow is designed
        to land. Once every producer is gone the cap lifts — leftover
        queue contents are finite, and local_idle requires pending==0,
        so a capped pump would leave this host unable to ever read
        idle (fleet-wide livelock via the all_idle gate)."""
        conns = getattr(self.transport, "active_connections", 0)
        if conns > 0 or getattr(self.transport, "ever_connected", False):
            # ever_connected catches a producer that connected and
            # vanished entirely between this loop's observations
            self._saw_remote = True
        producers_live = (
            any(t.is_alive() for t in self._actor_threads) or conns > 0)
        cap = 4 * self.dp_local * self._chunk if producers_live \
            else float("inf")
        while self._stage_n < cap:
            batch = self.transport.recv_experience(timeout=0.0)
            if batch is None:
                return
            n = int(batch["priorities"].shape[0])
            with self._lock:
                self._frames_local += int(batch.get("frames", n))
            self._stage.append(batch)
            self._stage_n += n

    def _pop_block(self) -> dict | None:
        """Take one [dp_local, chunk, ...] block off the stage."""
        need = self.dp_local * self._chunk
        if self._stage_n < need:
            return None
        fields = {
            k: np.concatenate([np.asarray(b[k]) for b in self._stage])
            for k in self._item_keys + ("priorities",)}
        take = {k: v[:need].reshape(self.dp_local, self._chunk,
                                    *v.shape[1:])
                for k, v in fields.items()}
        rest = {k: v[need:] for k, v in fields.items()}
        self._stage = [rest] if rest["priorities"].shape[0] else []
        self._stage_n -= need
        return take

    def _min_fill(self) -> int:
        return min(self.cfg.replay.min_fill, self.capacity // 2)

    def _warmup(self, chunk_steps: int) -> None:
        """AOT-compile the hot jits before actors start (same rationale
        as ApexDriver._warmup: the first add/train_many dispatch
        otherwise compiles for 20-40s inside the single-threaded round
        loop, during which nothing pumps the bounded transport queue
        and drop-oldest discards the early experience stream on every
        host). Abstract ShapeDtypeStructs with the real shardings stand
        in for the global ingest arrays — no cross-host data movement,
        and every process lowers the identical program at the same
        construction point."""
        cls = type(self.learner)
        sharding = NamedSharding(self.mesh, P("dp"))
        ptail = (self.cfg.replay.seg_transitions,) if self._frame_mode \
            else ()
        items = jax.tree.map(
            lambda t: jax.ShapeDtypeStruct(
                (self.dp, self._chunk) + t.shape, t.dtype,
                sharding=sharding),
            self._item_spec)
        pris = jax.ShapeDtypeStruct((self.dp, self._chunk) + ptail,
                                    np.float32, sharding=sharding)
        cls.add.lower(self.learner, self.state, items, pris).compile()
        cls.train_many.lower(self.learner, self.state,
                             chunk_steps).compile()
        if chunk_steps > 1:
            # the tail of a publish window dispatches single steps
            cls.train_many.lower(self.learner, self.state, 1).compile()

    # -- the lockstep round loop ------------------------------------------

    def run(self, total_env_frames: int | None = None,
            max_grad_steps: int = 10**9) -> dict:
        """Round loop. Termination derives from global frame/step counts
        only (wall clocks differ across hosts and would diverge the
        call sequences)."""
        cfg = self.cfg
        total = total_env_frames or cfg.total_env_frames
        per_actor = (total // max(jax.process_count(), 1)
                     // max(cfg.actors.num_actors, 1))
        publish_every = cfg.learner.publish_every
        chunk_steps = max(min(cfg.learner.train_chunk, publish_every), 1)

        threads = [threading.Thread(target=self._actor_thread,
                                    args=(i, per_actor),
                                    name=f"actor-{i}", daemon=True)
                   for i in range(cfg.actors.num_actors)]
        self._actor_threads = threads  # _pump_ingest's cap-lift check
        # self-describing JSONL: sampling semantics + storage layout
        # ride the stream itself (utils/metrics.log_run_header)
        log_run_header(self.metrics, cfg, self._grad_steps)
        self._warmup(chunk_steps)
        self.server.warmup(
            warmup_example(self.family, cfg, self.spec),
            extra_sizes=(cfg.actors.envs_per_actor,))
        evaluator = None
        if (jax.process_index() == 0 and cfg.eval_every_steps > 0
                and cfg.eval_episodes > 0):
            evaluator = threading.Thread(target=self._eval_loop,
                                         name="eval", daemon=True)
            evaluator.start()
        for t in threads:
            t.start()

        t0 = time.monotonic()
        filled = 0
        frames_global = float(self._frames_base)
        loss = float("nan")
        last_ckpt = self._grad_steps
        watchdog = StallWatchdog(
            cfg.multihost_watchdog_s,
            describe=lambda: (
                f"grad_steps={self._grad_steps} filled={filled} "
                f"frames_local={self._frames_local} "
                f"stage_n={self._stage_n}"))
        watchdog.start()
        global_size = jax.jit(
            lambda s: s.replay.size.sum(),
            out_shardings=jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec()))
        try:
            while True:
                self._pump_ingest()
                progressed = False
                # 0. ONE packed collective for this round's global control
                # values (three separate reductions would pay three
                # sequential DCN barrier round-trips per round).
                # `local_idle`: this host can never produce another ingest
                # block — actors finished/dead, no live remote actor-host
                # connections, transport drained. Deliberately independent
                # of the stage: a host stranded with a full block that OTHER
                # hosts can never match must still read as idle, or an
                # asymmetric drain spins every process forever.
                blocks_ready = 1.0 if self._stage_n >= \
                    self.dp_local * self._chunk else 0.0
                # boot grace: a host with NO local actors whose listening
                # transport has never seen a remote actor-host must not
                # read idle — at startup active_connections == 0 only
                # because producers are still booting, and an idle verdict
                # would terminate the fleet on round 1 with 0 grad steps.
                # Bounded (actors.remote_boot_grace_s): an actor-host job
                # that never launches must not pin the whole fleet in the
                # round loop forever. The deadline is host-local wall
                # clock, which is safe — it only changes this host's
                # REPORTED flag, not the collective call sequence.
                booting = (cfg.actors.num_actors == 0
                           and hasattr(self.transport, "active_connections")
                           and not self._saw_remote
                           and time.monotonic() - t0
                           < cfg.actors.remote_boot_grace_s)
                # quiesced() (socket transport) debounces transient
                # remote disconnects with a grace window; transports
                # without it (loopback) fall back to the connection
                # count, which for them never flickers
                remote_quiet = (
                    self.transport.quiesced()
                    if hasattr(self.transport, "quiesced")
                    else getattr(self.transport,
                                 "active_connections", 0) == 0)
                local_idle = 1.0 if (
                    not booting
                    and not any(t.is_alive() for t in threads)
                    and remote_quiet
                    and self.transport.pending == 0) else 0.0
                with self._lock:
                    frames_local = self._frames_local
                all_ready, all_idle, frames_global = multihost.global_stats(
                    self.mesh, blocks_ready, local_idle, float(frames_local))
                # resumed runs continue their frame budget from the
                # checkpointed global count (per-round counts restart
                # at 0 after a restore)
                frames_global += self._frames_base
                self._frames_global_latest = int(frames_global)
                # the packed collective returned: every peer is alive
                # and in lockstep as of this round
                watchdog.stamp()
                # 1. collective ingest, gated on EVERY host having a block
                if all_ready:
                    with self.obs.span("replay.add"):
                        block = self._pop_block()
                        items = multihost.make_global(
                            self.mesh,
                            {k: v for k, v in block.items()
                             if k != "priorities"})
                        pris = multihost.make_global(self.mesh,
                                                     block["priorities"])
                        self.state = self.learner.add(self.state, items,
                                                      pris)
                        filled = int(global_size(self.state))
                    progressed = True
                # 2. lockstep training, branch on global values only.
                # steps_per_frame_cap paces the learner to the GLOBAL
                # ingested frame count (frames_global comes from the
                # packed collective, so every process skips the same
                # rounds — the pacing itself is lockstep-safe)
                cap = cfg.learner.steps_per_frame_cap
                cap_bound = (cap is not None
                             and self._grad_steps >= cap * frames_global)
                if filled >= self._min_fill() and not cap_bound \
                        and self._grad_steps < max_grad_steps:
                    # whole chunks only; publication fires on boundary
                    # crossings (see ApexDriver._learner_loop_inner:
                    # snapping to exact publish multiples degrades
                    # dispatches to single steps). k is global-derived,
                    # so every process picks the same k — lockstep-safe.
                    done = self._grad_steps
                    k = chunk_steps if chunk_steps <= \
                        max_grad_steps - done else 1
                    # roofline attribution: AOT lower/compile of the
                    # exact train_many signature captures cost_analysis
                    # roofs and pre-populates the jit cache (lockstep-
                    # safe — compilation is deterministic across hosts)
                    if not self.obs.stage_attached("train"):
                        self.obs.stage_attach(
                            "train", k,
                            compile_fn=lambda: type(self.learner)
                            .train_many.lower(self.learner, self.state,
                                              k).compile())
                    with self.obs.stage_window("train", k):
                        with self.obs.span("learner.train", k=k):
                            self.state, m = self.learner.train_many(
                                self.state, k)
                            loss = float(m["loss"])  # blocks: honest timing
                    self._grad_steps += k
                    self.obs.set_learner_step(self._grad_steps)
                    progressed = True
                    if done // publish_every != \
                            self._grad_steps // publish_every:
                        with self.obs.span("learner.publish_params"):
                            pub = self._host_params()
                            self.server.update_params(pub,
                                                      self._grad_steps)
                            self.transport.publish_params(
                                pub, self._grad_steps)
                        with self._lock:
                            returns = list(self.episode_returns)
                        self.metrics.log(
                            self._grad_steps, loss=loss, replay_filled=filled,
                            frames_global=int(frames_global),
                            frames_local=frames_local,
                            avg_return=(float(np.mean(returns))
                                        if returns else None))
                        self.obs.gauge("replay_occupancy", filled)
                        self.obs.publish(self._grad_steps)
                # checkpoint on a grad-step cadence: _grad_steps is a
                # global value, so every process enters the collective
                # payload gather on the same round
                if (self.ckpt is not None
                        and self._grad_steps - last_ckpt
                        >= cfg.checkpoint_every):
                    self._save_checkpoint()
                    last_ckpt = self._grad_steps
                    watchdog.stamp()  # gathers can take minutes: the
                    # silence window restarts after a completed save
                # 3. global termination — all conditions derive from the
                # round-start packed collective, so every process breaks on
                # the same round. Guards against frame counts that never
                # reach `total` (lossy-transport drops, per-actor truncation
                # of the budget).
                if self._grad_steps >= max_grad_steps:
                    break
                if frames_global >= total and max_grad_steps >= 10**9:
                    break  # frame-budget run: actors are done
                if all_idle and not all_ready and (max_grad_steps >= 10**9
                                                   or filled
                                                   < self._min_fill()
                                                   or cap_bound):
                    # no host can ever produce experience again and the
                    # ingest gate cannot fire (stranded partial blocks can
                    # never complete); either there is no finite step target
                    # to chase, training can never start, or the frame-
                    # pacing cap binds forever (frames_global is final) —
                    # spinning helps nobody
                    break
                if not progressed:
                    # idle round: don't hammer the coordination service
                    # (sleep is host-local pacing, no collective is skipped)
                    time.sleep(0.05)
        except BaseException:
            # crash path: HOST-LOCAL teardown only. The clean-exit
            # sequence below runs collectives (final checkpoint gather,
            # orbax's synchronized close) that would hang on peers that
            # diverged or died with us; signal local actors/server and
            # let the exception surface (threads are daemon — process
            # exit is not blocked).
            watchdog.stop()
            self.stop_event.set()
            self.server.stop()
            self.obs.close(self._grad_steps)
            raise

        # final checkpoint BEFORE joining actors: the break is lockstep
        # (same round on every process), so the collective gather here
        # is aligned; actor joins are host-local and may take unequal
        # time. The watchdog stays armed through these final
        # collectives (a peer dying here hangs them too) and stops
        # only once no collective remains.
        watchdog.stamp()
        if self.ckpt is not None and self._grad_steps > last_ckpt:
            self._save_checkpoint(wait=True)
        if self.ckpt is not None:
            self.ckpt.close()
        watchdog.stop()
        self.stop_event.set()
        for t in threads:
            t.join(timeout=5)
        if evaluator is not None:
            evaluator.join(timeout=10)
        # short runs can finish inside one eval poll interval, and
        # eval_every_steps=0 disables the periodic thread entirely:
        # guarantee at least one greedy evaluation on process 0 while
        # the local inference server is still up (mirrors ApexDriver)
        if (jax.process_index() == 0 and cfg.eval_episodes > 0
                and self.last_eval is None and self._grad_steps > 0
                and self._eval_error is None):
            try:
                from ape_x_dqn_tpu.runtime.evaluation import (
                    final_eval_game)
                game = final_eval_game(cfg)
                res = self._make_eval_worker(game=game).run(
                    cfg.eval_episodes,
                    max_frames=cfg.eval_max_frames,
                    deadline_s=cfg.final_eval_deadline_s)
                if res is not None:
                    # the periodic eval thread's join above is
                    # timeout-bounded: it can still be mid-write when
                    # this teardown eval lands
                    with self._lock:
                        self.last_eval = res
                    self.metrics.log(
                        self._grad_steps,
                        avg_eval_return=res["mean_return"],
                        eval_episodes=res["episodes"],
                        eval_game=game or cfg.env.id)
            except Exception as e:  # noqa: BLE001
                with self._lock:
                    self._eval_error = e
        self.server.stop()
        self.obs.close(self._grad_steps)
        with self._lock:
            avg_ret = (float(np.mean(self.episode_returns))
                       if self.episode_returns else 0.0)
        return {
            "process": jax.process_index(),
            "frames": int(frames_global),
            "frames_local": self._frames_local,
            "grad_steps": self._grad_steps,
            "loss": loss,
            "replay_filled": filled,
            "avg_return": avg_ret,
            "wall_s": time.monotonic() - t0,
            "restored_step": self._restored_step,
            # grad-step of the last weight publication (0 = never):
            # lets callers (and dryrun_multichip's round-protocol
            # certification) assert the publish path actually fired
            "params_version": self.server.params_version,
            "actor_errors": [f"{i}: {e!r}" for i, e in self.actor_errors],
            "eval": self.last_eval,
            "eval_error": (repr(self._eval_error)
                           if self._eval_error else None),
        }
