"""Benchmark: Ape-X learner throughput on the flagship Atari config.

Measures the north-star number (BASELINE.md "Driver-set target"): learner
grad-steps/s at batch 512 on the dueling Nature-CNN (84x84x4 uint8), with
the prioritized sum-tree replay resident in HBM and the entire
sample->loss->optimize->priority-writeback cycle fused in one XLA jit
(the learner's `train_many`, a lax.scan over K steps per dispatch).

Prints exactly ONE JSON line on stdout:
  {"metric": "learner_grad_steps_per_s", "value": N, "unit": "steps/s",
   "vs_baseline": N / 19.0}
vs_baseline is relative to the reference's published learner throughput
(~19 grad-updates/s @ batch 512 on one GPU, Horgan et al. 2018 — see
BASELINE.md); the driver-set target is >=2.0x.

Secondary numbers (samples/s, inference forwards/s, compile/ingest times)
go to stderr so the stdout contract stays parseable.

The same line is persisted as the artifact of record (BENCH_LATEST.json,
or BENCH_SMOKE.json under --smoke) so the perf trajectory is machine-
readable, and --perf-gate turns it into a CI gate: the run exits nonzero
when the headline value falls below --gate-frac of the newest comparable
artifact.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ape_x_dqn_tpu.obs.profiling import device_peaks
from ape_x_dqn_tpu.utils.compile_cache import ensure_compile_cache


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def spread(runs) -> dict:
    """Median + min/max over repeated measurements: single-shot artifacts
    made round-over-round deltas uninterpretable (a −66% ingest
    'regression' between two rounds could not be told from noise
    without spread)."""
    # 4 significant digits, not 1 decimal: CPU-host rates sit around
    # 1 step/s where a fixed .1 rounding would eat a 5% A/B delta
    def r(x):
        return float(f"{float(x):.4g}")
    return {"median": r(np.median(runs)),
            "min": r(np.min(runs)),
            "max": r(np.max(runs))}


def _artifact_path(smoke: bool) -> str:
    """Artifact of record for this bench shape. Smoke runs (shrunken CI
    shapes) get their own file so a full-shape baseline is never
    compared against a smoke run or vice versa."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(here,
                        "BENCH_SMOKE.json" if smoke else "BENCH_LATEST.json")


def _load_baseline(smoke: bool) -> tuple[str | None, dict | None]:
    """Newest comparable bench artifact: the (path, summary) of the
    most recent BENCH_*.json whose content parses to a summary with
    metric/value. Handles both the raw single-line summary this script
    writes and the driver's capture format ({"parsed": <summary|null>,
    ...}) — a null `parsed` (the pre-ISSUE-8 trajectory) is skipped."""
    import glob
    here = os.path.dirname(os.path.abspath(__file__))
    if smoke:
        cands = [os.path.join(here, "BENCH_SMOKE.json")]
    else:
        cands = [p for p in glob.glob(os.path.join(here, "BENCH_*.json"))
                 if os.path.basename(p) != "BENCH_SMOKE.json"]
    cands = sorted((p for p in cands if os.path.exists(p)),
                   key=os.path.getmtime, reverse=True)
    for path in cands:
        try:
            with open(path) as fh:
                doc = json.load(fh)
        except (OSError, json.JSONDecodeError):
            continue
        if isinstance(doc, dict) and "parsed" in doc:
            doc = doc["parsed"]
        if isinstance(doc, dict) and "metric" in doc and "value" in doc:
            return path, doc
    return None, None


def _emit(result: dict, args) -> None:
    """The stdout contract AND the artifact of record: print the final
    single-line JSON summary, persist it next to this file (so driver
    BENCH_*.json captures and the perf-gate both get non-null,
    machine-readable data), then — under --perf-gate — exit nonzero if
    this run regressed below --gate-frac of the last artifact."""
    line = json.dumps(result)
    gated = getattr(args, "perf_gate", False)
    rc = _gate_exit(result, args) if gated else 0
    # a gate-FAILING run must not become the next baseline: persisting
    # it would ratchet the bar down so an equally-slow rerun passes
    if rc == 0:
        path = _artifact_path(getattr(args, "smoke", False))
        try:
            with open(path, "w") as fh:
                fh.write(line + "\n")
        except OSError as e:
            log(f"could not write bench artifact {path}: {e!r}")
    else:
        log("perf-gate: artifact of record NOT updated by this "
            "failing run")
    print(line, flush=True)
    if gated:
        raise SystemExit(rc)


def _gate_exit(result: dict, args) -> int:
    """Warn-only elsewhere, a hard gate here: the whole point of
    --perf-gate is a CI-visible nonzero exit on a real regression."""
    base_path, base = getattr(args, "_baseline", (None, None))
    if base is None:
        log("perf-gate: no comparable BENCH_*.json baseline — pass "
            "(this run's artifact seeds the trajectory)")
        return 0
    if base.get("metric") != result.get("metric"):
        log(f"perf-gate: baseline metric {base.get('metric')!r} != "
            f"{result.get('metric')!r} — not comparable, pass")
        return 0
    try:
        value = float(result["value"])
        baseline = float(base["value"])
    except (KeyError, TypeError, ValueError):
        log("perf-gate: non-numeric value(s) — not comparable, pass")
        return 0
    if baseline <= 0.0:
        log(f"perf-gate: degenerate baseline {baseline} — pass")
        return 0
    ratio = value / baseline
    if ratio < args.gate_frac:
        log(f"perf-gate FAIL: {result['metric']} {value:.4g} is "
            f"{ratio:.2f}x of baseline {baseline:.4g} "
            f"({base_path}) — below --gate-frac {args.gate_frac}")
        return 1
    log(f"perf-gate pass: {result['metric']} {value:.4g} is "
        f"{ratio:.2f}x of baseline {baseline:.4g} ({base_path})")
    return 0


def build_learner(capacity: int, batch_size: int, storage: str,
                  sample_chunk: int = 1, sample_prefetch: bool = False):
    from ape_x_dqn_tpu.configs import LearnerConfig, NetworkConfig
    from ape_x_dqn_tpu.envs.base import EnvSpec
    from ape_x_dqn_tpu.models import build_network
    from ape_x_dqn_tpu.replay.frame_ring import FrameRingReplay
    from ape_x_dqn_tpu.replay.prioritized import PrioritizedReplay
    from ape_x_dqn_tpu.runtime.family import build_learner as family_learner
    from ape_x_dqn_tpu.runtime.learner import transition_item_spec
    from ape_x_dqn_tpu.utils.rng import component_key

    spec = EnvSpec(obs_shape=(84, 84, 4), obs_dtype=np.dtype(np.uint8),
                   discrete=True, num_actions=18)
    # pre-flight fits-check via the drivers' own check_hbm_fits (one
    # source of truth for the budget policy): `--storage flat` at the
    # 2^20 default would allocate ~57GB and die in the allocator
    # mid-build without it
    from ape_x_dqn_tpu.utils.hbm import check_hbm_fits
    from ape_x_dqn_tpu.configs import ReplayConfig, get_config
    bcfg = get_config("pong").replace(
        replay=ReplayConfig(kind="prioritized", capacity=capacity,
                            storage=storage))
    try:
        check_hbm_fits(bcfg, spec.obs_shape, spec.obs_dtype,
                       param_count=1_700_000)
    except ValueError as e:
        raise SystemExit(f"{e}\n(or use --storage frame_ring)") from e
    ncfg = NetworkConfig(kind="nature_cnn", dueling=True)
    net = build_network(ncfg, spec)
    params = net.init(component_key(0, "net_init"),
                      jnp.zeros((1, 84, 84, 4), jnp.uint8))
    lcfg = LearnerConfig(batch_size=batch_size, sample_chunk=sample_chunk,
                         sample_prefetch=sample_prefetch)
    if storage == "frame_ring":
        replay = FrameRingReplay(capacity=capacity, seg_transitions=16,
                                 n_step=3, obs_shape=spec.obs_shape)
        replay_state = replay.init()
    else:
        replay = PrioritizedReplay(capacity=capacity)
        replay_state = replay.init(transition_item_spec(spec.obs_shape,
                                                        spec.obs_dtype))
    learner = family_learner(bcfg.replace(network=ncfg, learner=lcfg),
                             net, replay)
    state = learner.init(params, replay_state, component_key(0, "learner"))
    return net, learner, state, spec


def _flat_chunk(spec, chunk: int, rng) -> tuple[dict, object]:
    items = {
        "obs": jnp.asarray(
            rng.integers(0, 255, (chunk, *spec.obs_shape)), jnp.uint8),
        "action": jnp.asarray(
            rng.integers(0, spec.num_actions, chunk), jnp.int32),
        "reward": jnp.asarray(rng.normal(size=chunk), jnp.float32),
        "next_obs": jnp.asarray(
            rng.integers(0, 255, (chunk, *spec.obs_shape)), jnp.uint8),
        "discount": jnp.full(chunk, 0.99**3, jnp.float32),
    }
    return items, jnp.asarray(rng.uniform(0.1, 2.0, chunk), jnp.float32)


def _seg_chunk(replay, spec, g: int, rng) -> tuple[dict, object]:
    b, f = replay.B, replay.F
    items = {
        "seg_frames": jnp.asarray(
            rng.integers(0, 255, (g, f, *spec.obs_shape[:2])), jnp.uint8),
        "action": jnp.asarray(
            rng.integers(0, spec.num_actions, (g, b)), jnp.int32),
        "reward": jnp.asarray(rng.normal(size=(g, b)), jnp.float32),
        "discount": jnp.full((g, b), 0.99**3, jnp.float32),
        "next_off": jnp.full((g, b), 3, jnp.int32),
    }
    return items, jnp.asarray(rng.uniform(0.1, 2.0, (g, b)), jnp.float32)


def prefill(learner, state, spec, n_items: int, storage: str,
            chunk: int = 4096, repeats: int = 3):
    """Fill replay via the real `add` jit, and time the INGEST PATH
    separately from host data generation: one chunk of synthetic
    transitions is generated once, and every dispatch re-lands it from
    host memory (host->device DMA + add), which is what actor ingest
    actually costs the learner host."""
    replay = learner.replay
    rng = np.random.default_rng(0)
    if storage == "frame_ring":
        g = chunk // replay.B
        dev_items, dev_pris = _seg_chunk(replay, spec, g, rng)
        n_dispatch = n_items // (g * replay.B)
        per_dispatch = g * replay.B
        wire_bytes = sum(np.asarray(v).nbytes for v in dev_items.values())
    else:
        dev_items, dev_pris = _flat_chunk(spec, chunk, rng)
        n_dispatch = n_items // chunk
        per_dispatch = chunk
        wire_bytes = sum(np.asarray(v).nbytes for v in dev_items.values())
    # ascontiguousarray is load-bearing: a d2h view can be strided, and
    # device_put of a NON-contiguous 40MB host array was measured
    # ~300x slower than a contiguous one (18.8s vs 0.07s — a whole
    # multi-round 'ingest decline' was this staging artifact). Real
    # actor ingest always ships contiguous wire-decoded arrays.
    host_items = {k: np.ascontiguousarray(np.asarray(v))
                  for k, v in dev_items.items()}
    host_pris = np.ascontiguousarray(np.asarray(dev_pris))
    # compile once
    state = learner.add(state, dev_items, dev_pris)
    jax.block_until_ready(state.replay.tree)
    # measure in `repeats` equal sub-runs for median + spread
    per_run = max((n_dispatch - 1) // repeats, 1)
    rates = []
    for _ in range(repeats):
        t0 = time.monotonic()
        for _ in range(per_run):
            items = {k: jnp.asarray(v) for k, v in host_items.items()}
            state = learner.add(state, items, jnp.asarray(host_pris))
        jax.block_until_ready(state.replay.tree)
        rates.append(per_run * per_dispatch / (time.monotonic() - t0))
    log(f"ingest (h2d + add): {spread(rates)} items/s, "
        f"{wire_bytes / per_dispatch:,.0f} wire bytes/item "
        f"[{storage}]")
    return state, rates


def bench_add_device(learner, state, spec, storage: str,
                     chunk: int = 4096, repeats: int = 3,
                     dispatches: int = 8):
    """On-device add ceiling: the same `add` jit with the staged block
    ALREADY device-resident, so the h2d link is out of the picture.
    Separates the op's cost (scatter + sum-tree repair) from the
    host link (round-3 verdict missing #3 / next-round #8: 'PCIe fixes
    ingest' was extrapolation until the op itself was measured)."""
    replay = learner.replay
    rng = np.random.default_rng(1)
    if storage == "frame_ring":
        g = chunk // replay.B
        dev_items, dev_pris = _seg_chunk(replay, spec, g, rng)
        per_dispatch = g * replay.B
    else:
        dev_items, dev_pris = _flat_chunk(spec, chunk, rng)
        per_dispatch = chunk
    jax.block_until_ready(jax.tree.leaves(dev_items)[0])
    # same shapes as prefill -> add is already compiled
    state = learner.add(state, dev_items, dev_pris)
    jax.block_until_ready(state.replay.tree)
    rates = []
    for _ in range(repeats):
        t0 = time.monotonic()
        for _ in range(dispatches):
            state = learner.add(state, dev_items, dev_pris)
        jax.block_until_ready(state.replay.tree)
        rates.append(dispatches * per_dispatch / (time.monotonic() - t0))
    log(f"device-resident add: {spread(rates)} transitions/s "
        f"(block={per_dispatch}, h2d excluded) [{storage}]")
    return state, rates


def bench_learner(learner, state, steps_per_dispatch: int,
                  dispatches: int, repeats: int = 3,
                  trace_dir: str | None = None,
                  throttle_ms: float = 0.0):
    """throttle_ms injects a host-side sleep per timed dispatch — the
    perf-gate's test hook (an artificially slowed run must exit
    nonzero under --perf-gate); 0 is the real measurement."""
    # compile + warmup dispatch (excluded from timing AND the trace —
    # a 20-40s compile window would drown the steady-state capture)
    t0 = time.monotonic()
    state, m = learner.train_many(state, steps_per_dispatch)
    jax.block_until_ready(m["loss"])
    log(f"train_many compile+first dispatch: {time.monotonic() - t0:.1f}s "
        f"(loss={float(m['loss']):.4f})")
    rates = []
    for r in range(repeats):
        if trace_dir and r == 0:
            jax.profiler.start_trace(trace_dir)
        t0 = time.monotonic()
        try:
            for _ in range(dispatches):
                state, m = learner.train_many(state, steps_per_dispatch)
                if throttle_ms > 0.0:
                    time.sleep(throttle_ms / 1e3)
            jax.block_until_ready(m["loss"])
        finally:
            if trace_dir and r == 0:
                jax.profiler.stop_trace()
                log(f"profiler trace written to {trace_dir}")
        rates.append(steps_per_dispatch * dispatches
                     / (time.monotonic() - t0))
    assert np.isfinite(float(m["loss"])), "non-finite loss in steady state"
    return rates, state


def bench_stage_breakdown(learner, state, k: int, iters: int = 16,
                          repeats: int = 3) -> tuple[dict, object]:
    """Sample vs learn split of one macro-step, host-timed through the
    split sample_k/learn_k jits — the same dispatch the observability
    layer's traced path takes (obs/report.py prints the live-run twin
    of this number from span/replay.sample + span/learner.learn).
    block_until_ready inside each stage keeps the split honest against
    async dispatch; the fused train_many number above stays the
    throughput authority (the split forgoes overlap by construction)."""
    # warm both jits at this (state, k) signature
    sample, rng = learner.sample_k(state, k)
    jax.block_until_ready(sample)
    state, m = learner.learn_k(state._replace(rng=rng), sample, k)
    jax.block_until_ready(m["loss"])
    samp_ms, learn_ms = [], []
    for _ in range(repeats):
        ts = tl = 0.0
        for _ in range(iters):
            t0 = time.monotonic()
            sample, rng = learner.sample_k(state, k)
            jax.block_until_ready(sample)
            ts += time.monotonic() - t0
            t0 = time.monotonic()
            state, m = learner.learn_k(state._replace(rng=rng), sample, k)
            jax.block_until_ready(m["loss"])
            tl += time.monotonic() - t0
        samp_ms.append(1000.0 * ts / iters)
        learn_ms.append(1000.0 * tl / iters)
    log(f"stage breakdown (split sample_k/learn_k, k={k}): sample "
        f"{spread(samp_ms)} ms vs learn {spread(learn_ms)} ms "
        f"per macro-step")
    return ({"sample_ms": spread(samp_ms), "learn_ms": spread(learn_ms),
             "k": k}, state)


def train_step_flops_xla(learner, state,
                         steps_per_dispatch: int) -> float | None:
    """XLA's own FLOP count for one fused grad-step (compiler cost
    analysis of the train_many executable / scan length). On this TPU
    backend the compiler count omits most conv FLOPs (~0.9 vs ~47
    analytic GFLOP/step) — reported for cross-reference only; MFU uses
    the analytic count."""
    try:
        # .lower() via the class: the jitted wrapper's lower() does not
        # re-bind self the way its __call__ does
        compiled = type(learner).train_many.lower(
            learner, state, steps_per_dispatch).compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops = float(cost["flops"])
        return flops / steps_per_dispatch if flops > 0 else None
    except Exception as e:  # noqa: BLE001 - strictly best-effort
        log(f"cost_analysis unavailable: {e!r}")
        return None


def train_step_flops_analytic(batch_size: int, num_actions: int = 18,
                              dense: int = 512) -> float:
    """Analytic FLOP/step for the flagship dueling Nature-CNN train
    step (models/qnets.py shapes: 84x84x4 -> conv 32x8s4 -> 64x4s2 ->
    64x3s1 -> dense 512 -> dueling heads).

    Accounting: the double-DQN loss runs the online net on obs (with
    gradient: ~3x forward cost for fwd+bwd), the online net on
    next_obs, and the target net on next_obs (1x each) -> 5x one
    forward's MACs. 2 FLOPs per MAC. Elementwise/optimizer/replay ops
    are excluded (they are bandwidth-, not FLOP-bound)."""
    convs = [  # (out_h, out_w, c_out, k, c_in)
        (20, 20, 32, 8, 4),
        (9, 9, 64, 4, 32),
        (7, 7, 64, 3, 64),
    ]
    macs = sum(h * w * co * k * k * ci for h, w, co, k, ci in convs)
    macs += 7 * 7 * 64 * dense            # torso dense
    macs += dense * (num_actions + 1)     # dueling heads
    return 2.0 * macs * batch_size * 5.0


def bench_actor_pipeline(num_actors: int = 2, envs_per_actor: int = 16,
                         frames_per_actor: int = 2000) -> dict:
    """Aggregate actor env-frames/s through the REAL acting pipeline:
    vector actors (runtime/vector_actor.py) stepping synthetic-Atari
    envs, querying the batched inference server (`query_batch`, one
    K-item request per vector step), building n-step transitions and
    frame segments, shipping through a loopback transport. This is the
    second attested first-class metric (BASELINE.json "actor
    env-frames/sec"; the paper fleet sustains ~50k aggregate over 360
    actor cores — the number here scales with this host's cores)."""
    import threading

    from ape_x_dqn_tpu.comm.transport import LoopbackTransport
    from ape_x_dqn_tpu.configs import ActorConfig, EnvConfig, get_config
    from ape_x_dqn_tpu.envs import make_env
    from ape_x_dqn_tpu.models import build_network
    from ape_x_dqn_tpu.parallel.inference_server import (
        BatchedInferenceServer)
    from ape_x_dqn_tpu.runtime.family import warmup_example
    from ape_x_dqn_tpu.runtime.vector_actor import VectorActor
    from ape_x_dqn_tpu.utils.rng import component_key

    cfg = get_config("pong").replace(
        env=EnvConfig(id="catch", kind="synthetic_atari"),
        actors=ActorConfig(num_actors=num_actors,
                           envs_per_actor=envs_per_actor,
                           ingest_batch=50))
    probe = make_env(cfg.env, seed=0)
    net = build_network(cfg.network, probe.spec)
    params = net.init(component_key(0, "net_init"),
                      jnp.zeros((1, *probe.spec.obs_shape), jnp.uint8))
    # the server runs where ApexDriver puts it — the default device —
    # so this is the co-located topology the pong preset ships
    server = BatchedInferenceServer(
        net.apply, params, max_batch=cfg.inference.max_batch,
        deadline_ms=cfg.inference.deadline_ms)
    transport = LoopbackTransport()

    # drain ingest so the loopback queue never applies backpressure
    drained = {"batches": 0}
    stop = threading.Event()

    def drain():
        while not stop.is_set():
            if transport.recv_experience(timeout=0.1) is not None:
                drained["batches"] += 1

    drainer = threading.Thread(target=drain, daemon=True)
    drainer.start()
    try:
        server.warmup(warmup_example("dqn", cfg, probe.spec),
                      extra_sizes=(envs_per_actor,))
    except (AttributeError, NotImplementedError):
        pass
    actors = [VectorActor(cfg, i, server.query_batch, transport, seed=i)
              for i in range(num_actors)]
    frames = [0] * num_actors
    errors: list[Exception] = []

    def run_actor(i: int) -> None:
        try:
            frames[i] = actors[i].run(frames_per_actor)
        except Exception as e:  # noqa: BLE001 - re-raised below
            errors.append(e)

    threads = [threading.Thread(target=run_actor, args=(i,), daemon=True)
               for i in range(num_actors)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.monotonic() - t0
    stop.set()
    server.stop()
    if errors:
        # a dead actor would silently deflate the metric; fail instead
        raise RuntimeError(f"actor bench failed: {errors[0]!r}")
    st = server.stats
    return {
        "env_frames_per_s": sum(frames) / dt,
        "actors": num_actors,
        "envs_per_actor": envs_per_actor,
        "server_avg_batch": st["avg_batch"],
        "ingest_batches": drained["batches"],
    }


def _build_seq_learner(batch_size: int, sample_chunk: int,
                       sample_prefetch: bool, capacity: int = 4096,
                       lstm: int = 64, seq_len: int = 16,
                       obs_dim: int = 16):
    """Small vector-obs R2D2 learner + filled replay for the
    prefetch A/B (the recurrent family has the deepest sample stage —
    stored-state sequence gather — so it is where descent/backward
    overlap has the most to hide behind)."""
    from ape_x_dqn_tpu.configs import (LearnerConfig, NetworkConfig,
                                       ReplayConfig, RunConfig)
    from ape_x_dqn_tpu.models import ApeXLSTMQNet
    from ape_x_dqn_tpu.replay.prioritized import PrioritizedReplay
    from ape_x_dqn_tpu.replay.sequence import sequence_item_spec
    from ape_x_dqn_tpu.runtime.family import build_learner as family_learner
    from ape_x_dqn_tpu.utils.rng import component_key

    net = ApeXLSTMQNet(num_actions=18, lstm_size=lstm, dense=lstm,
                       compute_dtype="float32", mlp_torso=True)
    z = jnp.zeros((1, lstm), jnp.float32)
    params = net.init(component_key(0, "seq_net"),
                      jnp.zeros((1, seq_len, obs_dim), jnp.float32), (z, z))
    lcfg = LearnerConfig(batch_size=batch_size, n_step=2,
                         value_rescale=True, sample_chunk=sample_chunk,
                         sample_prefetch=sample_prefetch)
    rcfg = ReplayConfig(kind="sequence", seq_length=seq_len, burn_in=4)
    replay = PrioritizedReplay(capacity=capacity)
    spec = sequence_item_spec((obs_dim,), np.float32, seq_len, lstm)
    learner = family_learner(
        RunConfig(network=NetworkConfig(kind="lstm_q",
                                        compute_dtype="float32"),
                  learner=lcfg, replay=rcfg), net, replay)
    state = learner.init(params, replay.init(spec),
                         component_key(0, "seq_learner"))
    rng = np.random.default_rng(0)
    n = capacity
    items = {
        "obs": jnp.asarray(rng.normal(size=(n, seq_len, obs_dim)),
                           jnp.float32),
        "actions": jnp.asarray(rng.integers(0, 18, (n, seq_len)),
                               jnp.int32),
        "rewards": jnp.asarray(rng.normal(size=(n, seq_len)), jnp.float32),
        "terminals": jnp.zeros((n, seq_len), jnp.float32),
        "mask": jnp.ones((n, seq_len), jnp.float32),
        "init_c": jnp.zeros((n, lstm), jnp.float32),
        "init_h": jnp.zeros((n, lstm), jnp.float32),
    }
    state = learner.add(state, items,
                        jnp.asarray(rng.random(n) + 0.1, jnp.float32))
    return learner, state


def bench_prefetch_ab(args) -> dict:
    """A/B for the double-buffered sampler (LearnerConfig.
    sample_prefetch): per family (flat DQN + R2D2 sequence), measure
    grad-steps/s with prefetch OFF and ON, in BOTH orders (off->on then
    on->off on fresh learners) so a drift artifact in either direction
    is visible, median-of-`repeats` per arm. The adoption bar for
    flipping a preset default is a win outside the noise band in both
    orders (PERF.md 'Prefetch A/B')."""
    spd, disp = args.ab_steps_per_dispatch, args.ab_dispatches

    def _staleness(learner, state) -> float | None:
        """Measured priority-staleness fraction (obs/learning.py) from
        one extra already-compiled dispatch: the in-graph delta between
        descent-time and write-back-time priorities — identically 0 on
        the fused arm, the quantified one-macro-step lag under prefetch
        (the number ROADMAP item 3 said to measure, not assume)."""
        _, m = learner.train_many(state, spd)
        v = m.get("diag", {}).get("prio_staleness_frac")
        return None if v is None else float(f"{float(v):.4g}")

    def flat_arm(prefetch: bool) -> tuple[list[float], float | None]:
        _, learner, state, _spec = build_learner(
            args.ab_capacity, args.ab_batch_size, args.storage,
            args.sample_chunk, sample_prefetch=prefetch)
        state, _ = prefill(learner, state, _spec,
                           max(args.ab_capacity // 2, 4096), args.storage,
                           repeats=1)
        rates, state = bench_learner(learner, state, spd, disp,
                                     repeats=args.repeats)
        return rates, _staleness(learner, state)

    def seq_arm(prefetch: bool) -> tuple[list[float], float | None]:
        learner, state = _build_seq_learner(
            args.ab_batch_size, args.sample_chunk, prefetch)
        rates, state = bench_learner(learner, state, spd, disp,
                                     repeats=args.repeats)
        return rates, _staleness(learner, state)

    out = {"sample_chunk": args.sample_chunk,
           "batch_size": args.ab_batch_size,
           "steps_per_dispatch": spd}
    for name, arm in (("flat", flat_arm), ("sequence", seq_arm)):
        orders = {}
        for order in ("off_first", "on_first"):
            first = order == "off_first"
            a, a_stale = arm(not first)   # off when off_first
            b, b_stale = arm(first)       # on when off_first
            off, on = (a, b) if first else (b, a)
            off_st, on_st = ((a_stale, b_stale) if first
                             else (b_stale, a_stale))
            orders[order] = {"off": spread(off), "on": spread(on),
                             "prio_staleness_frac": {"off": off_st,
                                                     "on": on_st}}
            log(f"prefetch A/B [{name}/{order}]: off "
                f"{spread(off)} vs on {spread(on)} grad-steps/s "
                f"(prio staleness off={off_st} on={on_st})")
        d = [100.0 * (orders[o]["on"]["median"] / orders[o]["off"]["median"]
                      - 1.0) for o in orders]
        out[name] = {**orders,
                     "on_vs_off_pct": [round(x, 1) for x in d]}
        log(f"prefetch A/B [{name}]: on vs off "
            f"{[f'{x:+.1f}%' for x in d]} (order off-first, on-first)")
    return out


def _ingest_unit_spec(learner, spec, storage: str):
    """(item_spec, priority tail) for ONE staging unit — a frame
    segment (frame_ring) or a transition (flat) — mirroring the
    driver's staging geometry (runtime/family.py)."""
    if storage == "frame_ring":
        replay = learner.replay
        b, f = replay.B, replay.F
        item_spec = {
            "seg_frames": jax.ShapeDtypeStruct((f, *spec.obs_shape[:2]),
                                               np.uint8),
            "action": jax.ShapeDtypeStruct((b,), np.int32),
            "reward": jax.ShapeDtypeStruct((b,), np.float32),
            "discount": jax.ShapeDtypeStruct((b,), np.float32),
            "next_off": jax.ShapeDtypeStruct((b,), np.int32),
        }
        return item_spec, (b,), b
    item_spec = {
        "obs": jax.ShapeDtypeStruct(spec.obs_shape, np.uint8),
        "action": jax.ShapeDtypeStruct((), np.int32),
        "reward": jax.ShapeDtypeStruct((), np.float32),
        "next_obs": jax.ShapeDtypeStruct(spec.obs_shape, np.uint8),
        "discount": jax.ShapeDtypeStruct((), np.float32),
    }
    return item_spec, (), 1


def bench_live_soak(args, zero_copy: bool) -> dict:
    """THE live-vs-offline gap (ISSUE 3): grad-steps/s with a saturating
    concurrent ingest stream divided by grad-steps/s offline, on the
    same learner. The ingest thread replays one recorded wire payload
    through the driver's actual staging mechanics — the zero-copy
    pipelined stager (runtime/ingest.py: decode_into + double-buffered
    device_put + coalesced add_many) or a faithful replica of the
    legacy list-append + concatenate-per-flush + add-per-block path —
    sharing the state lock with the train_many dispatch loop exactly
    like runtime/driver.py does."""
    import threading

    from ape_x_dqn_tpu.comm.socket_transport import (
        WireBatch, decode_batch, encode_batch)
    from ape_x_dqn_tpu.runtime.ingest import IngestStager

    spd, disp = args.ab_steps_per_dispatch, args.ab_dispatches
    _, learner, state, spec = build_learner(
        args.ab_capacity, args.ab_batch_size, args.storage,
        args.sample_chunk)
    state, _ = prefill(learner, state, spec,
                       max(args.ab_capacity // 2, 4096), args.storage,
                       repeats=1)
    item_spec, ptail, unit_items = _ingest_unit_spec(learner, spec,
                                                     args.storage)
    keys = tuple(item_spec.keys()) + ("priorities",)
    n_wire = 8 if args.storage == "frame_ring" else 64  # units/message
    block = 2 * n_wire
    coalesce = 4
    rng = np.random.default_rng(3)
    wire = {}
    for k, s in item_spec.items():
        shape = (n_wire,) + tuple(s.shape)
        if np.issubdtype(np.dtype(s.dtype), np.integer):
            wire[k] = rng.integers(0, 3, size=shape).astype(s.dtype)
        else:
            wire[k] = rng.random(shape).astype(s.dtype)
    wire["priorities"] = (rng.random((n_wire,) + ptail) + 0.1).astype(
        np.float32)
    payload = encode_batch(wire)

    holder = {"state": state}
    lock = threading.Lock()
    counts = {"units": 0}

    # warm the two ingest graphs (single-block add, coalesced add_many)
    # and train_many before any timing starts
    zb = {k: jnp.zeros((block,) + tuple(s.shape), s.dtype)
          for k, s in item_spec.items()}
    zp = jnp.zeros((block,) + ptail, jnp.float32)
    holder["state"] = learner.add(holder["state"], zb, zp)
    gb = {k: jnp.zeros((coalesce, block) + tuple(s.shape), s.dtype)
          for k, s in item_spec.items()}
    gp = jnp.zeros((coalesce, block) + ptail, jnp.float32)
    holder["state"] = learner.add_many(holder["state"], gb, gp)
    holder["state"], m = learner.train_many(holder["state"], spd)
    jax.block_until_ready(m["loss"])

    def ship(views, g):
        shape = (g, block) if g > 1 else (block,)
        staged = {k: jax.device_put(v.reshape(shape + v.shape[1:]))
                  for k, v in views.items()}
        pris = staged.pop("priorities")
        handles = list(staged.values()) + [pris]
        with lock:
            if g > 1:
                holder["state"] = learner.add_many(holder["state"],
                                                   staged, pris)
            else:
                holder["state"] = learner.add(holder["state"], staged,
                                              pris)
        counts["units"] += g * block
        return handles

    stop = threading.Event()

    def ingest_zero_copy():
        stager = IngestStager(item_spec, ptail, block, coalesce, 2, ship)
        while not stop.is_set():
            stager.put(WireBatch(payload))

    def ingest_legacy():
        # faithful replica of the pre-rewrite driver staging: decode to
        # fresh dicts, append, concatenate the backlog per flush, carry
        # the rest, one add dispatch (and lock acquisition) per block
        stage, stage_n = [], 0
        while not stop.is_set():
            stage.append(decode_batch(payload))
            stage_n += n_wire
            while stage_n >= block:
                fields = {
                    k: np.concatenate([np.asarray(b[k]) for b in stage])
                    for k in keys}
                take = {k: v[:block] for k, v in fields.items()}
                rest = {k: v[block:] for k, v in fields.items()}
                stage = [rest] if rest["priorities"].shape[0] else []
                stage_n -= block
                items = {k: jnp.asarray(v) for k, v in take.items()
                         if k != "priorities"}
                pris = jnp.asarray(take["priorities"])
                with lock:
                    holder["state"] = learner.add(holder["state"], items,
                                                  pris)
                counts["units"] += block

    def timed_run() -> float:
        t0 = time.monotonic()
        for _ in range(disp):
            with lock:
                holder["state"], mm = learner.train_many(holder["state"],
                                                         spd)
            jax.block_until_ready(mm["loss"])
        return spd * disp / (time.monotonic() - t0)

    offline = [timed_run() for _ in range(args.repeats)]
    thread = threading.Thread(
        target=ingest_zero_copy if zero_copy else ingest_legacy,
        daemon=True)
    t_live = time.monotonic()
    thread.start()
    live = [timed_run() for _ in range(args.repeats)]
    stop.set()
    thread.join(timeout=10)
    dt = time.monotonic() - t_live
    ingest_rate = counts["units"] * unit_items / dt
    gap = spread(live)["median"] / spread(offline)["median"]
    tag = "new" if zero_copy else "old"
    log(f"live soak [{tag}]: offline {spread(offline)} vs live "
        f"{spread(live)} grad-steps/s -> live_gap "
        f"{gap:.3f}; concurrent ingest {ingest_rate:,.0f} items/s")
    return {"offline": spread(offline), "live": spread(live),
            "live_gap": float(f"{gap:.4g}"),
            "ingest_items_per_s": float(f"{ingest_rate:.4g}")}


def bench_ingest_ab(args) -> dict:
    """A/B the staging rewrite: live_gap (live / offline grad-steps/s
    under a saturating concurrent ingest stream) with the legacy
    staging vs the zero-copy pipelined stager, in BOTH orders on fresh
    learners (old->new then new->old) so drift artifacts are visible
    either way. Adoption bar (ISSUE 3): live_gap ~0.51 -> >= 0.75 in
    both orders with offline grad-steps/s inside the +/-5% noise band."""
    out = {"batch_size": args.ab_batch_size, "storage": args.storage,
           "steps_per_dispatch": args.ab_steps_per_dispatch}
    for order in ("old_first", "new_first"):
        first_new = order == "new_first"
        a = bench_live_soak(args, zero_copy=first_new)
        b = bench_live_soak(args, zero_copy=not first_new)
        old, new = (b, a) if first_new else (a, b)
        out[order] = {"old": old, "new": new}
        log(f"ingest A/B [{order}]: live_gap old {old['live_gap']} -> "
            f"new {new['live_gap']}; offline old "
            f"{old['offline']['median']} vs new "
            f"{new['offline']['median']} grad-steps/s")
    out["live_gap_old"] = [out[o]["old"]["live_gap"]
                           for o in ("old_first", "new_first")]
    out["live_gap_new"] = [out[o]["new"]["live_gap"]
                           for o in ("old_first", "new_first")]
    return out


def _wire_ab_messages(n_msgs: int, n_wire: int = 8, f: int = 12,
                      b: int = 12) -> list[dict]:
    """Atari-like synthetic frame-ring experience messages: a static
    background plus a few sprites drifting a few pixels per frame, so
    temporally adjacent frames XOR to sparse deltas — the structure the
    wire codec exploits. Pure-noise frames would understate the ratio
    (noise is incompressible); real Atari frames compress better still
    (larger static regions)."""
    rng = np.random.default_rng(11)
    hw = (84, 84)
    bg = rng.integers(0, 40, hw, dtype=np.uint8)
    msgs = []
    for m in range(n_msgs):
        segs = np.empty((n_wire, f, *hw), np.uint8)
        for u in range(n_wire):
            for i in range(f):
                t = (m * n_wire + u) * f + i
                fr = bg.copy()
                for s in range(4):
                    x = (3 * t * (s + 1)) % (hw[0] - 8)
                    y = (2 * t * (s + 2)) % (hw[1] - 8)
                    fr[x:x + 8, y:y + 8] = 60 + 40 * s
                segs[u, i] = fr
        msgs.append({
            "seg_frames": segs,
            "action": rng.integers(0, 18, (n_wire, b)).astype(np.int32),
            "reward": rng.random((n_wire, b)).astype(np.float32),
            "discount": np.ones((n_wire, b), np.float32),
            "next_off": rng.integers(0, f, (n_wire, b)).astype(np.int32),
            "priorities": (rng.random((n_wire, b)) + 0.1).astype(
                np.float32),
            "frames": n_wire * f,
        })
    return msgs


def bench_wire_ab(args) -> dict:
    """A/B the wire codec (comm/socket_transport delta-deflate) over a
    REAL loopback socket pair: bytes/transition and transitions/s for
    raw vs codec, both orders on fresh pairs, median-of-`--repeats` —
    plus a bandwidth-capped arm (sender paced to --wire-ab-cap-mb MB/s,
    the round-4 measured live link rate) showing items/s scaling with
    the compression ratio, which is what the codec buys on a real NIC
    (loopback has no bandwidth ceiling, so the uncapped arms mostly
    measure encode/decode CPU)."""
    import threading

    from ape_x_dqn_tpu.comm.socket_transport import (
        SocketIngestServer, SocketTransport)

    n_wire, f, b = 8, 12, 12
    msgs = _wire_ab_messages(6, n_wire, f, b)
    iters = 8  # message-list replays per timed run
    total_units = len(msgs) * iters * n_wire
    transitions = total_units * b

    def arm(codec: str, cap_mb_s: float | None = None) -> dict:
        srv = SocketIngestServer("127.0.0.1", 0, wire_codec=codec)
        tr = SocketTransport("127.0.0.1", srv.port, wire_codec=codec)
        dest = {k: np.zeros_like(v) for k, v in msgs[0].items()
                if isinstance(v, np.ndarray)}
        got = {"units": 0}
        done = threading.Event()

        def consume() -> None:
            while got["units"] < total_units:
                m = srv.recv_experience(timeout=10)
                if m is None:
                    break
                # land through the one-copy staging path so decode cost
                # (inflate + delta-undo) is inside the measurement
                m.decode_into(dest, 0, 0, n_wire)
                got["units"] += m.rows
            done.set()

        thread = threading.Thread(target=consume, daemon=True)
        t0 = time.monotonic()
        thread.start()
        for _ in range(iters):
            for batch in msgs:
                tr.send_experience(batch)
                if cap_mb_s:
                    # token-bucket pacing: a cap_mb_s link would have
                    # taken bytes_out / cap seconds to carry what we
                    # shipped so far — sleep off the surplus
                    lag = (tr.bytes_out / (cap_mb_s * 1e6)
                           - (time.monotonic() - t0))
                    if lag > 0:
                        time.sleep(lag)
        done.wait(timeout=60)
        dt = time.monotonic() - t0
        out = {
            "items_per_s": transitions / dt,
            "bytes_per_transition": tr.bytes_out / transitions,
            "ratio": tr.wire_compression_ratio,
            "negotiated": tr.negotiated_codec,
            "encode_ms_total": round(tr.encode_ms, 1),
        }
        tr.close()
        srv.stop()
        assert got["units"] == total_units, \
            f"consumer saw {got['units']}/{total_units} units"
        return out

    out = {"denomination": "frame_ring", "units_per_msg": n_wire,
           "transitions_per_unit": b, "cap_mb_s": args.wire_ab_cap_mb}
    for order in ("raw_first", "codec_first"):
        arms = ("raw", "delta-deflate") if order == "raw_first" \
            else ("delta-deflate", "raw")
        runs: dict[str, list] = {"raw": [], "delta-deflate": []}
        last = {}
        for _ in range(args.repeats):
            for codec in arms:
                r = arm(codec)
                runs[codec].append(r["items_per_s"])
                last[codec] = r
        out[order] = {
            codec: {"items_per_s": spread(runs[codec]),
                    "bytes_per_transition": round(
                        last[codec]["bytes_per_transition"], 1),
                    "ratio": round(last[codec]["ratio"], 2),
                    "negotiated": last[codec]["negotiated"]}
            for codec in runs}
        log(f"wire A/B [{order}]: raw "
            f"{out[order]['raw']['bytes_per_transition']} B/transition "
            f"@ {spread(runs['raw'])} items/s vs codec "
            f"{out[order]['delta-deflate']['bytes_per_transition']} "
            f"B/transition @ {spread(runs['delta-deflate'])} items/s "
            f"(ratio {out[order]['delta-deflate']['ratio']}x)")
    capped: dict[str, list] = {"raw": [], "delta-deflate": []}
    for _ in range(args.repeats):
        for codec in ("raw", "delta-deflate"):
            capped[codec].append(
                arm(codec, cap_mb_s=args.wire_ab_cap_mb)["items_per_s"])
    out["bandwidth_capped"] = {
        codec: spread(capped[codec]) for codec in capped}
    out["bandwidth_capped"]["speedup"] = round(
        spread(capped["delta-deflate"])["median"]
        / spread(capped["raw"])["median"], 2)
    log(f"wire A/B capped @ {args.wire_ab_cap_mb} MB/s: raw "
        f"{spread(capped['raw'])} vs codec "
        f"{spread(capped['delta-deflate'])} items/s -> "
        f"{out['bandwidth_capped']['speedup']}x")
    return out


# -- shared-memory transport lane (comm/shm_transport.py; ISSUE 18) ----------


def _shm_artifact_path(smoke: bool) -> str:
    """Artifact of record for the shm-transport lane. Same smoke/full
    split as the main bench: a CI smoke run only ever gates against a
    smoke baseline."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(here, "SHM_SMOKE.json" if smoke
                        else "SHM_LATEST.json")


def _load_shm_baseline(smoke: bool, producers: int, units_per_msg: int
                       ) -> tuple[str | None, dict | None]:
    """Newest COMPARABLE shm artifact: same smoke class, same contended
    producer count, same units/msg. The contended items/s bakes in how
    many writers fight over the ingest queue and how much each message
    carries — a cross-shape gate would fire on a shape change, not a
    regression."""
    path = _shm_artifact_path(smoke)
    try:
        with open(path) as fh:
            doc = json.load(fh)
    except (OSError, json.JSONDecodeError):
        return None, None
    if not (isinstance(doc, dict) and "metric" in doc
            and "value" in doc):
        return None, None
    if (doc.get("producers") != producers
            or doc.get("units_per_msg") != units_per_msg):
        log(f"shm gate: {os.path.basename(path)} is "
            f"{doc.get('producers')}p@{doc.get('units_per_msg')}u, "
            f"this run is {producers}p@{units_per_msg}u — not "
            f"comparable, skipped")
        return None, None
    return path, doc


def bench_shm_ab(args) -> None:
    """A/B the same-host shared-memory transport (comm/shm_transport)
    against plain TCP loopback with the default delta-deflate codec,
    over REAL SocketIngestServer/SocketTransport pairs: ingest items/s
    shm-on vs shm-off, both orders on fresh pairs, an uncapped arm
    (one producer) and a contended arm (--shm-ab-producers concurrent
    producer transports fighting over one ingest queue — the topology
    the shm plane exists for: N same-host actor processes feeding one
    learner). Every arm closes its own accounting (offered ==
    delivered + torn + dropped, zero torn slots delivered) before its
    number counts. Adoption bar (ISSUE 18): shm >= --shm-ab-bar x TCP
    items/s on the contended arm in BOTH orders. Writes
    SHM_LATEST.json (SHM_SMOKE.json under --smoke; PERF.md
    'Shared-memory transport')."""
    import threading

    from ape_x_dqn_tpu.comm.socket_transport import (
        SocketIngestServer, SocketTransport, encode_batch)

    n_wire, f, b = 8, 12, 12
    n_msgs = 2 if args.smoke else 4
    # enough replays that the timed window dwarfs the fixed connect +
    # hello + shm-negotiation cost (~tens of ms); at the measured
    # per-message costs an arm run is still well under a second
    iters = 16 if args.smoke else 24  # message-list replays per producer
    msgs = _wire_ab_messages(n_msgs, n_wire, f, b)
    # slot must hold one raw-encoded message (shm slots carry raw
    # payloads — the codec exists to buy bandwidth, and shm has no
    # wire), plus framing slack
    slot_bytes = len(encode_batch(msgs[0], "raw")) + 4096
    producers_contended = max(2, args.shm_ab_producers)

    def arm(shm: bool, producers: int) -> dict:
        srv = SocketIngestServer(
            "127.0.0.1", 0, wire_codec="delta-deflate", shm=shm,
            shm_slots=args.shm_ab_slots, shm_slot_bytes=slot_bytes,
            shm_param_bytes=1 << 20)
        trs = [SocketTransport("127.0.0.1", srv.port,
                               wire_codec="delta-deflate", shm=shm,
                               shm_slots=args.shm_ab_slots,
                               shm_slot_bytes=slot_bytes)
               for _ in range(producers)]
        dest = {k: np.zeros_like(v) for k, v in msgs[0].items()
                if isinstance(v, np.ndarray)}
        offered = producers * len(msgs) * iters
        got = {"msgs": 0, "units": 0, "t_last": 0.0}
        sent = threading.Event()

        def consume() -> None:
            # drain until the producers are done AND the queue is dry;
            # land through the one-copy staging path so decode cost
            # (inflate for TCP, memcpy for shm slots) is inside the
            # measurement, and release each slot back to its ring
            while True:
                m = srv.recv_experience(timeout=0.25)
                if m is None:
                    if sent.is_set():
                        return
                    continue
                m.decode_into(dest, 0, 0, n_wire)
                got["msgs"] += 1
                got["units"] += m.rows
                got["t_last"] = time.monotonic()
                rel = getattr(m, "release", None)
                if rel is not None:
                    rel()

        def produce(tr: SocketTransport) -> None:
            for _ in range(iters):
                for batch in msgs:
                    tr.send_experience(batch)

        consumer = threading.Thread(target=consume, daemon=True)
        workers = [threading.Thread(target=produce, args=(tr,),
                                    daemon=True)
                   for tr in trs]
        t0 = time.monotonic()
        consumer.start()
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=120)
        sent.set()
        consumer.join(timeout=60)
        dt = max(got["t_last"] - t0, 1e-9)
        client_dropped = sum(tr.dropped for tr in trs)
        posts = sum(tr.shm_posts for tr in trs)
        falls = sum(tr.shm_fallbacks for tr in trs)
        out = {
            "items_per_s": got["units"] * b / dt,
            "delivered": got["msgs"],
            "offered": offered,
            "dropped": srv.dropped + client_dropped,
            "torn": srv.shm_torn_slots,
            "shm_posts": posts,
            "shm_fallbacks": falls,
            "shm_bytes_in": srv.shm_bytes_in,
            "negotiated": all(tr.shm_negotiated for tr in trs) if shm
            else not any(tr.shm_negotiated for tr in trs),
        }
        # accounting closure is a hard precondition for the arm's
        # number to count — a lane that silently lost batches would
        # report a throughput nobody actually got
        assert (got["msgs"] + srv.dropped + client_dropped
                + srv.shm_torn_slots == offered), \
            f"accounting open: {out}"
        assert srv.shm_torn_slots == 0, \
            f"torn slots detected on loopback: {out}"
        if shm:
            assert posts + falls + client_dropped == offered, \
                f"shm post accounting open: {out}"
            assert srv.shm_doorbells == posts, \
                f"doorbells {srv.shm_doorbells} != posts {posts}"
            assert srv.shm_slots_inflight == 0, \
                f"{srv.shm_slots_inflight} slots still inflight"
        for tr in trs:
            tr.close()
        srv.stop()
        return out

    pooled: dict[tuple[str, str], list] = {
        (a, c): [] for a in ("shm", "tcp")
        for c in ("uncapped", "contended")}
    out: dict = {"denomination": "frame_ring", "units_per_msg": n_wire,
                 "transitions_per_unit": b, "n_msgs": n_msgs,
                 "iters": iters, "slots": args.shm_ab_slots,
                 "slot_bytes": slot_bytes,
                 "producers": producers_contended}
    speedups = {}
    for order in ("shm_first", "tcp_first"):
        arms = ("shm", "tcp") if order == "shm_first" \
            else ("tcp", "shm")
        runs: dict[tuple[str, str], list] = {
            k: [] for k in pooled}
        last: dict[tuple[str, str], dict] = {}
        for _ in range(args.repeats):
            for name in arms:
                for cname, producers in (("uncapped", 1),
                                         ("contended",
                                          producers_contended)):
                    r = arm(name == "shm", producers)
                    runs[(name, cname)].append(r["items_per_s"])
                    pooled[(name, cname)].append(r["items_per_s"])
                    last[(name, cname)] = r
        out[order] = {
            f"{name}_{cname}": {
                "items_per_s": spread(runs[(name, cname)]),
                "delivered": last[(name, cname)]["delivered"],
                "offered": last[(name, cname)]["offered"],
                "dropped": last[(name, cname)]["dropped"],
                "torn": last[(name, cname)]["torn"],
            }
            for (name, cname) in runs}
        speedups[order] = round(
            spread(runs[("shm", "contended")])["median"]
            / spread(runs[("tcp", "contended")])["median"], 2)
        log(f"shm A/B [{order}]: contended shm "
            f"{spread(runs[('shm', 'contended')])} vs tcp "
            f"{spread(runs[('tcp', 'contended')])} items/s -> "
            f"{speedups[order]}x (uncapped shm "
            f"{spread(runs[('shm', 'uncapped')])['median']:,.0f} vs "
            f"tcp {spread(runs[('tcp', 'uncapped')])['median']:,.0f})")

    ok = all(s >= args.shm_ab_bar for s in speedups.values())
    result = {
        "metric": "shm_items_per_s_contended",
        "value": float(f"{spread(pooled[('shm', 'contended')])['median']:.6g}"),
        "unit": "items/s",
        "ok": ok,
        "smoke": bool(args.smoke),
        "speedup_contended": speedups,
        "speedup_uncapped": round(
            spread(pooled[("shm", "uncapped")])["median"]
            / spread(pooled[("tcp", "uncapped")])["median"], 2),
        **out,
    }
    line = json.dumps(result)
    gated = getattr(args, "perf_gate", False)
    rc = 0
    if gated:
        args._baseline = _load_shm_baseline(
            args.smoke, producers_contended, n_wire)
        rc = _gate_exit(result, args)
    if not ok:
        log(f"shm: adoption bar NOT met (contended speedup "
            f"{speedups} vs >= {args.shm_ab_bar}x in both orders)")
        rc = rc or 1
    if rc == 0 or not gated:
        if ok:
            path = _shm_artifact_path(args.smoke)
            try:
                with open(path, "w") as fh:
                    fh.write(line + "\n")
            except OSError as e:
                log(f"could not write shm artifact {path}: {e!r}")
    else:
        log("shm perf-gate: artifact of record NOT updated by this "
            "failing run")
    print(line, flush=True)
    raise SystemExit(rc)


# -- param-plane codec lane (comm/param_codec.py; ISSUE 19) ------------------


def _params_artifact_path(smoke: bool) -> str:
    """Artifact of record for the param-codec lane (same smoke/full
    split as the other side lanes)."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(here, "PARAMS_SMOKE.json" if smoke
                        else "PARAMS_LATEST.json")


def _load_params_baseline(smoke: bool, subs: int, param_count: int
                          ) -> tuple[str | None, dict | None]:
    """Newest COMPARABLE param-codec artifact: same smoke class, same
    subscriber fan-out, same parameter count. The bytes-per-publish
    reduction bakes in the tree's leaf mix and how many peers each
    publish reaches — a cross-shape gate would fire on a shape change,
    not a regression."""
    path = _params_artifact_path(smoke)
    try:
        with open(path) as fh:
            doc = json.load(fh)
    except (OSError, json.JSONDecodeError):
        return None, None
    if not (isinstance(doc, dict) and "metric" in doc
            and "value" in doc):
        return None, None
    if (doc.get("subs") != subs
            or doc.get("param_count") != param_count):
        log(f"params gate: {os.path.basename(path)} is "
            f"{doc.get('subs')}subs@{doc.get('param_count')}params, "
            f"this run is {subs}subs@{param_count}params — not "
            f"comparable, skipped")
        return None, None
    return path, doc


def _params_tree(smoke: bool, rng) -> dict:
    """A nature-CNN-shaped f32 tree (the real broadcast payload shape:
    conv stacks + one dominant dense matrix + small heads). The smoke
    tree keeps the same leaf mix at ~1/8 the dense size."""
    dense_in, dense_out = (3136, 512) if not smoke else (392, 128)
    shapes = {
        "conv1_w": (8, 8, 4, 32), "conv1_b": (32,),
        "conv2_w": (4, 4, 32, 64), "conv2_b": (64,),
        "conv3_w": (3, 3, 64, 64), "conv3_b": (64,),
        "dense_w": (dense_in, dense_out), "dense_b": (dense_out,),
        "adv_w": (dense_out, 18), "adv_b": (18,),
        "val_w": (dense_out, 1), "val_b": (1,),
    }
    return {k: (rng.standard_normal(s) * 0.05).astype(np.float32)
            for k, s in shapes.items()}


def _params_step(tree: dict, rng) -> dict:
    """One simulated training update: heavy-tailed per-leaf deltas
    (g^3 — gradient-noise-shaped, small-dominated with outliers), the
    regime the delta+q8 codec is built for. Dense gaussian deltas are
    the codec's worst case (~2.7x); measured training deltas are not
    gaussian."""
    return {k: (w + 0.01 * rng.standard_normal(w.shape) ** 3
                ).astype(np.float32)
            for k, w in tree.items()}


def bench_params_ab(args) -> None:
    """A/B the param-plane codec (comm/param_codec.py, ISSUE 19):
    weight broadcast to --params-ab-subs REAL push subscribers
    (SocketIngestServer/SocketTransport pairs over loopback),
    delta-q8 vs raw, both orders on fresh pairs, median-of-`--repeats`
    per arm. Per arm: wire bytes per publish (the metric the codec
    exists to cut), publish->receive latency across healthy peers, and
    a token-bucket-capped run (--params-ab-cap-mb simulated link)
    where the byte saving converts to publish rate. Adoption bar:
    delta-q8 cuts bytes/publish by >= --params-ab-bar x in BOTH
    orders. Also runs once each: a quantized-policy parity smoke
    (greedy actions after a delta chain vs the fp32 tree) and a
    slow-subscriber isolation arm (one wedged never-reading peer must
    not move healthy-peer latency; its deposits supersede, counted).
    Writes PARAMS_LATEST.json (PARAMS_SMOKE.json under --smoke;
    PERF.md 'Param-plane codec')."""
    import socket as socket_mod
    import threading

    from ape_x_dqn_tpu.comm.socket_transport import (
        MSG_HELLO, SocketIngestServer, SocketTransport, _recv_msg,
        _send_msg)

    rng = np.random.default_rng(7)
    tree = _params_tree(args.smoke, rng)
    param_count = int(sum(w.size for w in tree.values()))
    n_subs = max(2, args.params_ab_subs)
    n_pubs = 4 if args.smoke else 8
    exp_batch = {"obs": np.zeros((4, 4), np.float32),
                 "action": np.zeros((4,), np.int32),
                 "priorities": np.ones((4,), np.float32),
                 "actor": 0, "frames": 4}

    def _wait(pred, timeout=20.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if pred():
                return True
            time.sleep(0.002)
        return False

    class _Sub:
        """One push subscriber + its poller thread: tracks the newest
        version seen and when it landed."""

        def __init__(self, port: int, codec: str):
            self.tr = SocketTransport(
                "127.0.0.1", port, params_push=True, param_codec=codec)
            self.ver = -1
            self.t_seen = 0.0
            self.stop = False
            self.tr.send_experience(exp_batch)  # connect + negotiate
            self.th = threading.Thread(target=self._poll, daemon=True)
            self.th.start()

        def _poll(self) -> None:
            while not self.stop:
                p, v = self.tr.poll_pushed_params()
                if p is None:
                    time.sleep(0.002)
                    continue
                self.ver, self.t_seen = v, time.monotonic()

        def close(self) -> None:
            self.stop = True
            self.th.join(timeout=5)
            self.tr.close()

    def arm(codec: str, cap_mb_s: float = 0.0) -> dict:
        srv = SocketIngestServer("127.0.0.1", 0, param_codec=codec)
        subs = [_Sub(srv.port, codec) for _ in range(n_subs)]
        lat_ms: list[float] = []
        try:
            for _ in range(n_subs):  # the connect batches
                srv.recv_experience(timeout=5.0)
            assert all(s.tr.params_push_negotiated for s in subs)
            coded = codec != "raw"
            assert all(s.tr.param_codec_negotiated == coded
                       for s in subs), "param codec negotiation failed"
            cur = tree
            srv.publish_params(cur, 0)  # seed publish, untimed
            assert _wait(lambda: all(s.ver >= 0 for s in subs)), \
                "seed publish never reached every subscriber"
            b0 = srv.param_bytes_out
            r0 = srv.param_raw_bytes_out
            t0 = time.monotonic()
            for v in range(1, n_pubs + 1):
                cur = _params_step(cur, rng)
                t_pub = time.monotonic()
                srv.publish_params(cur, v)
                assert _wait(lambda: all(s.ver >= v for s in subs)), \
                    f"publish v{v} never reached every subscriber"
                lat_ms.extend((s.t_seen - t_pub) * 1e3 for s in subs)
                if cap_mb_s:
                    # token-bucket pacing: a cap_mb_s link would have
                    # taken bytes/cap seconds to carry what the
                    # broadcast shipped so far — sleep off the surplus
                    lag = ((srv.param_bytes_out - b0)
                           / (cap_mb_s * 1e6)
                           - (time.monotonic() - t0))
                    if lag > 0:
                        time.sleep(lag)
            dt = max(time.monotonic() - t0, 1e-9)
            wire = srv.param_bytes_out - b0
            raw = srv.param_raw_bytes_out - r0
            drops = srv.param_push_queue_drops
            # accounting closure: ack-paced healthy peers consume every
            # version — any drop/resync here means the lane itself is
            # broken and its numbers do not count
            assert sum(drops.values()) == 0, f"unexpected drops {drops}"
            assert srv.param_resyncs == 0, "unexpected resyncs"
            return {
                "bytes_per_publish": wire / n_pubs,
                "raw_bytes_per_publish": raw / n_pubs,
                "ratio": srv.param_compression_ratio,
                "publishes_per_s": n_pubs / dt,
                "latency_ms": lat_ms,
            }
        finally:
            for s in subs:
                s.close()
            srv.stop()

    def isolation_arm() -> dict:
        """Healthy fan-out with one wedged (never-reading, tiny
        SO_RCVBUF) raw subscriber riding along: healthy-peer latency
        must not move, the wedged peer's deposits supersede (counted),
        and the broadcast never serializes behind its dead socket."""
        srv = SocketIngestServer("127.0.0.1", 0, param_codec="delta-q8")
        subs = [_Sub(srv.port, "delta-q8") for _ in range(n_subs)]
        ws = socket_mod.socket()
        clean: list[float] = []
        wedged: list[float] = []
        try:
            for _ in range(n_subs):
                srv.recv_experience(timeout=5.0)
            cur = tree
            srv.publish_params(cur, 0)
            assert _wait(lambda: all(s.ver >= 0 for s in subs))
            ver = 0

            def round_trip(sink: list[float]) -> None:
                nonlocal cur, ver
                cur = _params_step(cur, rng)
                ver += 1
                t_pub = time.monotonic()
                srv.publish_params(cur, ver)
                v = ver
                assert _wait(lambda: all(s.ver >= v for s in subs)), \
                    f"healthy subscriber starved at v{v}"
                sink.extend((s.t_seen - t_pub) * 1e3 for s in subs)

            for _ in range(n_pubs):
                round_trip(clean)
            # wedge: negotiate params_push as a raw peer (big full
            # blobs fill its buffers fastest), then never read again
            ws.setsockopt(socket_mod.SOL_SOCKET,
                          socket_mod.SO_RCVBUF, 4096)
            ws.connect(("127.0.0.1", srv.port))
            _send_msg(ws, MSG_HELLO, json.dumps(
                {"codecs": ["raw"], "params_push": True}).encode())
            ack = _recv_msg(ws)
            assert ack is not None, "wedged peer hello got no ack"
            # publish until the wedged peer's sender is provably stuck
            # (its one-deep cell starts superseding), then measure
            for i in range(64):
                round_trip(wedged if i >= 4 else [])
                if srv.param_push_queue_drops["superseded"] > 0 \
                        and len(wedged) >= n_pubs * n_subs:
                    break
            drops = srv.param_push_queue_drops
            assert drops["superseded"] > 0, \
                f"wedged peer never superseded a deposit: {drops}"
            med_clean = float(np.median(clean))
            med_wedged = float(np.median(wedged))
            # isolation bar: a wedged peer must not serialize the
            # broadcast — generous absolute floor for loopback jitter
            assert med_wedged <= max(5.0 * med_clean, 250.0), \
                (f"healthy-peer latency moved with a wedged peer: "
                 f"{med_clean:.1f}ms -> {med_wedged:.1f}ms")
            return {"healthy_latency_ms_clean": round(med_clean, 2),
                    "healthy_latency_ms_wedged": round(med_wedged, 2),
                    "superseded_drops": drops["superseded"]}
        finally:
            ws.close()
            for s in subs:
                s.close()
            srv.stop()

    def parity_smoke() -> dict:
        """Quantized-policy learning parity (PARITY.md row): greedy
        actions from a delta-q8 chain-reconstructed MLP vs the fp32
        tree it tracks. The chain error is bounded (<= half a quant
        step per leaf, non-accumulating by construction), so greedy
        argmax agreement must stay >= 0.99 over random states."""
        from ape_x_dqn_tpu.comm.param_codec import (ParamBlobProvider,
                                                    ParamChainDecoder)
        prng = np.random.default_rng(11)
        dims = (64, 128, 128, 18)
        w = {f"l{i}": {"w": (prng.standard_normal((a, b)) * 0.3
                             ).astype(np.float32),
                       "b": np.zeros((b,), np.float32)}
             for i, (a, b) in enumerate(zip(dims[:-1], dims[1:]))}

        def greedy(params: dict, x: np.ndarray) -> np.ndarray:
            h = x
            for i in range(len(dims) - 1):
                h = h @ params[f"l{i}"]["w"] + params[f"l{i}"]["b"]
                if i < len(dims) - 2:
                    h = np.maximum(h, 0.0)
            return h.argmax(axis=1)

        provider = ParamBlobProvider("bfloat16", "delta-q8", 8)
        decoder = ParamChainDecoder()
        have = -1
        for v in range(13):  # one full + a 12-step delta chain
            if v:
                w = {k: {n: (a + 0.01 * prng.standard_normal(
                    a.shape) ** 3).astype(np.float32)
                    for n, a in lw.items()} for k, lw in w.items()}
            provider.publish(w, v)
            payload, _kind, ver, _cost = provider.coded_reply(
                0, have, 0)
            status, _t, ver, _ep = decoder.apply(payload)
            assert status == "full", f"unexpected {status} at v{v}"
            have = ver
        states = prng.standard_normal((512, dims[0])).astype(np.float32)
        ref = greedy(w, states)
        got = greedy(decoder._tree(), states)
        agree = float((ref == got).mean())
        err = max(float(np.abs(w[k][n] - decoder._tree()[k][n]).max())
                  for k in w for n in w[k])
        assert agree >= 0.99, \
            f"greedy parity {agree:.4f} < 0.99 (max param err {err:.2e})"
        return {"greedy_agreement": round(agree, 4),
                "max_param_err": float(f"{err:.3g}"),
                "chain_len": 12, "states": 512}

    pooled: dict[str, list] = {"delta-q8": [], "raw": []}
    out: dict = {"subs": n_subs, "param_count": param_count,
                 "publishes": n_pubs,
                 "cap_mb_s": args.params_ab_cap_mb}
    reductions = {}
    for order in ("codec_first", "raw_first"):
        arms = ("delta-q8", "raw") if order == "codec_first" \
            else ("raw", "delta-q8")
        runs: dict[str, list] = {"delta-q8": [], "raw": []}
        last: dict[str, dict] = {}
        capped: dict[str, list] = {"delta-q8": [], "raw": []}
        for _ in range(args.repeats):
            for codec in arms:
                r = arm(codec)
                runs[codec].append(r["bytes_per_publish"])
                pooled[codec].append(r["bytes_per_publish"])
                last[codec] = r
                r_cap = arm(codec, cap_mb_s=args.params_ab_cap_mb)
                capped[codec].append(r_cap["publishes_per_s"])
        out[order] = {
            codec: {
                "bytes_per_publish": spread(runs[codec]),
                "ratio": round(last[codec]["ratio"], 2),
                "latency_ms_p50": round(
                    float(np.median(last[codec]["latency_ms"])), 2),
                "capped_publishes_per_s": spread(capped[codec]),
            } for codec in runs}
        reductions[order] = round(
            spread(runs["raw"])["median"]
            / spread(runs["delta-q8"])["median"], 2)
        log(f"params A/B [{order}]: delta-q8 "
            f"{spread(runs['delta-q8'])['median']:,.0f} vs raw "
            f"{spread(runs['raw'])['median']:,.0f} bytes/publish -> "
            f"{reductions[order]}x cut (capped link: "
            f"{spread(capped['delta-q8'])['median']:.2f} vs "
            f"{spread(capped['raw'])['median']:.2f} publishes/s)")

    out["isolation"] = isolation_arm()
    out["parity"] = parity_smoke()
    log(f"params isolation: healthy p50 "
        f"{out['isolation']['healthy_latency_ms_clean']}ms clean vs "
        f"{out['isolation']['healthy_latency_ms_wedged']}ms wedged "
        f"({out['isolation']['superseded_drops']} superseded); "
        f"parity: {out['parity']['greedy_agreement']:.4f} greedy "
        f"agreement, max param err {out['parity']['max_param_err']}")

    ok = all(r >= args.params_ab_bar for r in reductions.values())
    result = {
        "metric": "param_broadcast_bytes_reduction",
        "value": round(min(reductions.values()), 2),
        "unit": "x",
        "ok": ok,
        "smoke": bool(args.smoke),
        "reduction": reductions,
        **out,
    }
    line = json.dumps(result)
    gated = getattr(args, "perf_gate", False)
    rc = 0
    if gated:
        args._baseline = _load_params_baseline(
            args.smoke, n_subs, param_count)
        rc = _gate_exit(result, args)
    if not ok:
        log(f"params: adoption bar NOT met (bytes-per-publish cut "
            f"{reductions} vs >= {args.params_ab_bar}x in both orders)")
        rc = rc or 1
    if rc == 0 or not gated:
        if ok:
            path = _params_artifact_path(args.smoke)
            try:
                with open(path, "w") as fh:
                    fh.write(line + "\n")
            except OSError as e:
                log(f"could not write params artifact {path}: {e!r}")
    else:
        log("params perf-gate: artifact of record NOT updated by this "
            "failing run")
    print(line, flush=True)
    raise SystemExit(rc)


# chaos-lane availability recorded before the remediation plane (and
# the wedged-actor fault) existed: the PERF.md "Chaos lane (round 10)"
# number the remediation-on arm must hold even with the EXTRA fault in
# its schedule. A hard floor, not a ratchet — it never moves down.
_CHAOS_AVAIL_FLOOR = 0.822


def _chaos_artifact_path(smoke: bool) -> str:
    """Artifact of record for the chaos lane. Same smoke/full split as
    the main bench: a CI smoke run only ever gates against a smoke
    baseline."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(here, "CHAOS_SMOKE.json" if smoke
                        else "CHAOS_LATEST.json")


def _load_chaos_baseline(smoke: bool, window_s: float, clients: int
                         ) -> tuple[str | None, dict | None]:
    """Newest COMPARABLE chaos artifact: same smoke class, same timed
    window and sender-fleet size. Availability bakes in what fraction
    of the window the fault schedule occupies — a cross-shape gate
    would fire on a schedule change, not a regression."""
    path = _chaos_artifact_path(smoke)
    try:
        with open(path) as fh:
            doc = json.load(fh)
    except (OSError, json.JSONDecodeError):
        return None, None
    if not (isinstance(doc, dict) and "metric" in doc
            and "value" in doc):
        return None, None
    if (doc.get("window_s") != window_s
            or doc.get("clients") != clients):
        log(f"chaos gate: {os.path.basename(path)} is "
            f"{doc.get('window_s')}s@{doc.get('clients')} clients, "
            f"this run is {window_s}s@{clients} — not comparable, "
            f"skipped")
        return None, None
    try:
        value = float(doc["value"])
    except (TypeError, ValueError):
        return None, None
    if value > 1.0:
        # availability is remediated-vs-clean: a recorded value above
        # 1.0 means the remediated arm got LUCKY against its own clean
        # run, not that remediation beats no-faults. Ratcheting on such
        # a fluke makes the gate demand luck forever (a 1.423 baseline
        # once required availability >= 0.996 of every later run) —
        # clamp the gate at the semantic ceiling, keep the raw artifact
        log(f"chaos gate: baseline {value} exceeds the semantic "
            f"ceiling for an availability ratio — gating against 1.0")
        doc = dict(doc, value=1.0)
    return path, doc


def bench_chaos_ab(args) -> dict:
    """A/B/C the elastic fleet runtime under fault injection: the same
    sender fleet pushes experience through a chaos proxy for a fixed
    wall-clock window — once over a clean link, and twice through the
    full fault schedule (a garble phase, a link cut, a learner kill +
    restart under a new epoch, and a WEDGED sender: silent but not
    dead, the fault only a heartbeat/progress watchdog can see). The
    chaos arm runs the drill with the remediation plane off, so the
    wedged sender stays lost for the rest of the window; the
    remediated arm runs the identical drill with a RemediationEngine
    (runtime/remediation.py, enforce mode) watching per-sender send
    progress and restarting the wedged slot. The headline number is
    the remediated arm's availability: its ingest throughput as a
    fraction of the clean arm's, with every outage INSIDE the timed
    window — gated against the pre-remediation floor recorded in
    PERF.md (the engine must at least buy back the extra fault it is
    given). Also reports reconnect latencies and the fault attribution
    counters the lane asserts on."""
    import threading

    from ape_x_dqn_tpu.comm.socket_transport import (
        SocketIngestServer, SocketTransport)
    from ape_x_dqn_tpu.configs import RemediationConfig
    from ape_x_dqn_tpu.runtime.remediation import (Actuators,
                                                   RemediationEngine)
    from tools.chaos import ChaosProxy
    from tools.chaos.faults import ThreadWedge

    n_wire, f, b = 8, 12, 12
    msgs = _wire_ab_messages(4, n_wire, f, b)
    window_s = args.chaos_ab_seconds
    n_clients = 2

    def _converged(c) -> bool:
        # a client still inside its backoff window needs a few polls
        # before a pull lands on the new incarnation
        for _ in range(30):
            c.get_params()
            if c.epoch == 2:
                return True
            time.sleep(0.1)
        return False

    class _ObsSink:
        """Minimal obs facade for the engine (the bench has no Obs)."""

        def __init__(self):
            self.ctr: dict[str, int] = {}

        def count(self, name, n=1):
            self.ctr[name] = self.ctr.get(name, 0) + n

        def gauge(self, name, value):
            pass

    def arm(chaos: bool, remediate: bool = False) -> dict:
        srv = SocketIngestServer("127.0.0.1", 0, epoch=1)
        port = srv.port
        proxy = ChaosProxy("127.0.0.1", port, seed=17)
        srv.publish_params({"w": np.float32(0)}, 0)
        live = {"srv": srv}
        clients = [SocketTransport("127.0.0.1", proxy.port,
                                   reconnect_base_s=0.01,
                                   reconnect_cap_s=0.3,
                                   connect_timeout=2.0)
                   for _ in range(n_clients)]
        stop = threading.Event()
        rows = {"n": 0}
        decode_errs_prior = {"n": 0}  # from incarnations already stopped
        rows_lock = threading.Lock()
        wedge = ThreadWedge()
        # per-sender DELIVERY progress: the staleness signal the
        # remediation supervisor reads (the miniature of the driver's
        # heartbeat watchdog). Only a send that actually went out
        # advances the slot — a wedged sender AND a sender stuck
        # dropping into a backoff window both read as stale.
        last_send = [time.monotonic()] * n_clients

        def pump(c, k):
            i = 0
            while not stop.is_set():
                if k == 0:
                    # the wedge's opt-in checkpoint: sender 0 freezes
                    # here (silent, socket open) while engaged
                    wedge.checkpoint(timeout=0.25)
                    if wedge.engaged:
                        continue
                d0 = c.dropped
                c.send_experience(msgs[(k + i) % len(msgs)])
                if c.dropped == d0:  # delivered, not dropped
                    last_send[k] = time.monotonic()
                i += 1
                time.sleep(0.002)

        eng = None
        obs_sink = _ObsSink()
        # forensics (obs/blackbox.py, ISSUE 17): the remediated arm
        # gives every sender a flight recorder plus one for the
        # learner side, so the drill leaves the same evidence a real
        # fleet would — the lane asserts the postmortem bundle
        # attributes the injected fault by name
        recs = rec_learner = None
        fdir = ""
        if remediate:
            import tempfile

            from ape_x_dqn_tpu.obs.blackbox import FlightRecorder

            fdir = tempfile.mkdtemp(prefix="chaos_forensics_")
            recs = [FlightRecorder(obs_sink, peer=f"chaos-sender-{k}",
                                   out_dir=fdir)
                    for k in range(n_clients)]
            rec_learner = FlightRecorder(obs_sink, peer="chaos-learner",
                                         out_dir=fdir)
        if remediate:
            def _restart(slot: int, staleness_s: float) -> bool:
                # the driver's supervised slot respawn, approximated
                # in place: a real restart builds a fresh actor thread
                # (no wedge) and a fresh transport (no pending
                # backoff). Releasing the wedge resumes the pump at
                # its next checkpoint; kick() collapses a backoff
                # window left over from the outage.
                wedged = slot == 0 and wedge.engaged
                if wedged:
                    wedge.release()
                kicked = clients[slot].kick()
                # every restart decision archives the victim's ring —
                # the driver's supervisor contract, miniaturized
                recs[slot].record("supervisor_restart",
                                  component=f"sender-{slot}",
                                  staleness_s=round(staleness_s, 3),
                                  wedged=wedged, kicked=kicked)
                recs[slot].dump("supervisor_restart",
                                component=f"sender-{slot}")
                return wedged or kicked

            eng = RemediationEngine(
                RemediationConfig(mode="enforce", hysteresis_ticks=1,
                                  cooldown_s=0.25, budget_per_min=60.0),
                obs_sink, None, Actuators(restart_actor=_restart))

        def supervise():
            # the driver's supervisor tick, miniaturized: per-sender
            # progress staleness feeds the engine's safety rule
            while not stop.is_set():
                time.sleep(0.05)
                now = time.monotonic()
                for k in range(n_clients):
                    staleness = now - last_send[k]
                    # 0.2s of delivery silence is 100x the healthy
                    # 2ms inter-send gap
                    if staleness > 0.2:
                        eng.remediate_stale_actor(k, staleness)

        def drain():
            while not stop.is_set():
                m = live["srv"].recv_experience(timeout=0.1)
                if m is not None:
                    with rows_lock:
                        rows["n"] += m.rows
            # post-window flush so both arms count queued residue
            while True:
                m = live["srv"].recv_experience(timeout=0.05)
                if m is None:
                    return
                with rows_lock:
                    rows["n"] += m.rows

        threads = [threading.Thread(target=pump, args=(c, k),
                                    daemon=True)
                   for k, c in enumerate(clients)]
        if eng is not None:
            threads.append(threading.Thread(target=supervise,
                                            daemon=True))
        drainer = threading.Thread(target=drain, daemon=True)
        t0 = time.monotonic()
        drainer.start()
        for t in threads:
            t.start()
        if chaos:
            # fault schedule inside the window: degrade, cut, kill —
            # and a sender that wedges AT the kill, the worst case: a
            # wedge inside the outage is indistinguishable from outage
            # loss until the fleet recovers, and an unremediated one
            # never comes back (it stays silent through the entire
            # recovery tail). A remediated one is restarted off its
            # progress staleness while everything is down anyway, so
            # the wedge costs the remediated arm ~nothing.
            time.sleep(window_s * 0.25)
            proxy.set_fault(garble_rate=0.05)
            time.sleep(window_s * 0.25)
            proxy.clean()
            proxy.cut()
            decode_errs_prior["n"] = srv.wire_decode_errors
            if rec_learner is not None:
                # the injected faults, recorded as the victims would
                # record them: the learner sees its own kill coming
                # (srv.stop is this drill's SIGKILL), the wedged
                # sender's ring keeps the wedge engage
                rec_learner.record("kill", component="learner", epoch=1)
                rec_learner.dump("kill", component="learner")
                recs[0].record("wedge", component="sender-0")
            srv.stop()
            wedge.engage()  # wedged-not-dead: silent, socket open
            time.sleep(window_s * 0.10)  # the outage
            srv2 = SocketIngestServer("127.0.0.1", port, epoch=2)
            srv2.publish_params({"w": np.float32(1)}, 0)
            live["srv"] = srv2
            time.sleep(window_s * 0.40)
        else:
            time.sleep(window_s)
        stop.set()
        wedge.release()  # let a still-wedged pump observe stop
        for t in threads:
            t.join(timeout=2)
        drainer.join(timeout=5)
        dt = time.monotonic() - t0
        lat = sorted(x for c in clients
                     for x in c.reconnect_latencies)
        out = {
            "rows_per_s": rows["n"] * b / dt,
            "reconnects": sum(c.reconnects for c in clients),
            "reconnect_latency_ms": {
                "median": round(1000 * lat[len(lat) // 2], 1)
                if lat else None,
                "max": round(1000 * lat[-1], 1) if lat else None,
            },
            "drop_reasons": {
                k: sum(c.drop_reasons[k] for c in clients)
                for k in clients[0].drop_reasons},
            "epochs_converged": all(map(_converged, clients))
            if chaos else None,
            "wire_decode_errors": decode_errs_prior["n"]
            + live["srv"].wire_decode_errors,
        }
        if eng is not None:
            out["remediation"] = eng.summary()
            out["remediation_actions"] = obs_sink.ctr.get(
                "remediation_actions", 0)
        if recs is not None:
            # bundle the drill's black boxes and ask the report for
            # the root cause: the lane's artifact records whether the
            # attributed component IS one of the injected faults
            from ape_x_dqn_tpu.obs import postmortem as _pm
            from ape_x_dqn_tpu.obs import report as _report

            bpath = os.path.join(fdir, "POSTMORTEM.json")
            bundle = _pm.build_bundle(fdir, out_path=bpath,
                                      obs=obs_sink)
            root = _report.postmortem_root_cause(bundle) or {}
            anom = root.get("anomaly") or {}
            term = root.get("terminal") or {}
            injected = ("sender-0", "learner")
            attributed = (anom.get("component") in injected
                          or term.get("component") in injected)
            rc_line = _report.format_postmortem(
                bundle).splitlines()[-1]
            out["postmortem"] = {
                "bundle": bpath,
                "dumps": len(bundle["dumps"]),
                "skipped_dumps": bundle["skipped_dumps"],
                "bundles_counted": obs_sink.ctr.get(
                    "postmortem_bundles", 0),
                "root_cause": rc_line,
                "attributes_fault": bool(attributed),
            }
            log(f"chaos forensics: {out['postmortem']['dumps']} dumps "
                f"-> {bpath}; {rc_line}")
        for c in clients:
            c.close()
        proxy.stop()
        live["srv"].stop()
        return out

    out: dict = {"window_s": window_s, "clients": n_clients,
                 "transitions_per_unit": b}
    clean_runs, chaos_runs, rem_runs = [], [], []
    for _ in range(args.repeats):
        clean = arm(chaos=False)
        chaos = arm(chaos=True)
        rem = arm(chaos=True, remediate=True)
        clean_runs.append(clean["rows_per_s"])
        chaos_runs.append(chaos["rows_per_s"])
        rem_runs.append(rem["rows_per_s"])
        out["clean"], out["chaos"] = clean, chaos
        out["remediated"] = rem
    out["clean"]["rows_per_s"] = spread(clean_runs)
    out["chaos"]["rows_per_s"] = spread(chaos_runs)
    out["remediated"]["rows_per_s"] = spread(rem_runs)
    out["availability"] = round(
        spread(chaos_runs)["median"] / spread(clean_runs)["median"], 3)
    out["availability_remediated"] = round(
        spread(rem_runs)["median"] / spread(clean_runs)["median"], 3)
    log(f"chaos A/B/C: clean {spread(clean_runs)} rows/s, chaos "
        f"{spread(chaos_runs)} rows/s (availability "
        f"{out['availability']}), remediated {spread(rem_runs)} "
        f"rows/s (availability {out['availability_remediated']}, "
        f"{out['remediated'].get('remediation_actions', 0)} actions) — "
        f"reconnect median "
        f"{out['chaos']['reconnect_latency_ms']['median']} ms, "
        f"decode errors {out['chaos']['wire_decode_errors']}, "
        f"epochs converged {out['chaos']['epochs_converged']}")
    return out


def bench_learn_health(args) -> None:
    """Learning-health smoke lane (ISSUE 10): short REAL training runs
    (one per env family = tenant) through the single-process driver
    with the obs plane on, all appending to ONE metrics JSONL. The
    stream is then summarized in-process: the lane's verdict per game
    is `obs/report.py check_violations` over its tenant's gauges, and
    the artifact is SUITE_LEARN-shaped (games/scores/per_game/complete)
    so suite tooling can diff health the way it diffs scores. The CI
    gate is `python -m ape_x_dqn_tpu.obs.report <jsonl> --check`
    (tests/run_chunked.sh) — the online LearnMonitor stays warn-only."""
    from ape_x_dqn_tpu.configs import (EnvConfig, LearnerConfig,
                                       NetworkConfig, ObsConfig,
                                       ReplayConfig, get_config)
    from ape_x_dqn_tpu.obs import report as obs_report
    from ape_x_dqn_tpu.runtime.single_process import train_single_process
    from ape_x_dqn_tpu.utils.metrics import Metrics

    here = os.path.dirname(os.path.abspath(__file__))
    jsonl = os.path.join(here, "LEARN_HEALTH_SMOKE.jsonl")
    if os.path.exists(jsonl):
        os.remove(jsonl)  # Metrics appends; one artifact per lane run
    games = ["catch", "pong"]
    per_game: dict[str, dict] = {}
    scores: dict[str, float] = {}
    complete = True
    for game in games:
        cfg = get_config("pong").replace(
            env=EnvConfig(id=game, kind="synthetic_atari"),
            network=NetworkConfig(kind="nature_cnn", dueling=True,
                                  compute_dtype="float32"),
            replay=ReplayConfig(kind="prioritized", capacity=2048,
                                min_fill=300),
            learner=LearnerConfig(batch_size=16, n_step=3,
                                  target_sync_every=16, sample_chunk=2),
            obs=ObsConfig(enabled=True, publish_every_steps=50,
                          heartbeat_timeout_s=120.0))
        metrics = Metrics(log_path=jsonl)
        t0 = time.monotonic()
        out = train_single_process(cfg, total_env_frames=args.lh_frames,
                                   metrics=metrics, train_every=2)
        metrics.close()
        wall = time.monotonic() - t0
        log(f"learn-health [{game}]: {out['grad_steps']} grad-steps / "
            f"{out['frames']} frames in {wall:.1f}s, avg_return "
            f"{out['avg_return']:.2f}")
        summary = obs_report.summarize(obs_report.load_records(jsonl))
        tenant = summary["tenants"].get(game, {})
        events = [e for e in summary["learn_events"]
                  if e.get("tenant") == game]
        violations = obs_report.check_violations(summary)
        per_game[game] = {
            "game": game,
            "frames": out["frames"],
            "grad_steps": out["grad_steps"],
            "avg_return": round(out["avg_return"], 3),
            "wall_s": round(wall, 1),
            "learn": {k: float(f"{float(v):.4g}")
                      for k, v in sorted(tenant.items())},
            "degradation_events": len(events),
            "healthy": not violations,
        }
        scores[game] = round(out["avg_return"], 3)
        complete = (complete and out["grad_steps"] > 0 and bool(tenant))
    summary = obs_report.summarize(obs_report.load_records(jsonl))
    violations = obs_report.check_violations(summary)
    healthy_games = sum(1 for p in per_game.values() if p["healthy"])
    result = {
        "metric": "learn_health_games_healthy",
        "value": round(healthy_games / len(games), 3),
        "unit": "frac",
        "games": games,
        "scores": scores,
        "per_game": per_game,
        "complete": complete,
        "violations": violations,
        "degradation_events": len(summary["learn_events"]),
        "metrics_jsonl": os.path.basename(jsonl),
    }
    line = json.dumps(result)
    path = os.path.join(here, "LEARN_HEALTH_SMOKE.json")
    try:
        with open(path, "w") as fh:
            fh.write(line + "\n")
    except OSError as e:
        log(f"could not write learn-health artifact {path}: {e!r}")
    log(f"learn-health metrics JSONL -> {jsonl} (gate with `python -m "
        f"ape_x_dqn_tpu.obs.report {os.path.basename(jsonl)} --check`)")
    print(line, flush=True)
    # exit nonzero only when the RUNS failed to produce the plane; an
    # unhealthy-but-present plane is the report --check gate's call
    raise SystemExit(0 if complete else 1)


_BLACKBOX_RATIO_FLOOR = 0.95  # recorder-on / recorder-off grad-steps/s


def _blackbox_artifact_path(smoke: bool) -> str:
    here = os.path.dirname(os.path.abspath(__file__))
    name = "BLACKBOX_SMOKE.json" if smoke else "BLACKBOX_LATEST.json"
    return os.path.join(here, name)


def _load_blackbox_baseline(smoke: bool, frames: int
                            ) -> tuple[str | None, dict | None]:
    """Newest COMPARABLE blackbox artifact: same smoke class and same
    training-run length. The on/off ratio is workload-relative already,
    but a different frame budget shifts the JIT-warmup / steady-state
    mix — a cross-shape gate would fire on a budget change, not a
    recorder regression."""
    path = _blackbox_artifact_path(smoke)
    try:
        with open(path) as fh:
            doc = json.load(fh)
    except (OSError, json.JSONDecodeError):
        return None, None
    if not (isinstance(doc, dict) and "metric" in doc
            and "value" in doc):
        return None, None
    if doc.get("frames") != frames:
        log(f"blackbox gate: {os.path.basename(path)} is "
            f"{doc.get('frames')} frames, this run is {frames} — not "
            f"comparable, skipped")
        return None, None
    return path, doc


def bench_blackbox_ab(args) -> None:
    """Flight-recorder overhead A/B (ISSUE 17): the same short REAL
    training run through the single-process driver with the obs plane
    on, once with the FlightRecorder live (crash hooks installed,
    publish/stall/perf events recorded into the ring) and once with
    ``ObsConfig.blackbox=False`` (NULL_BLACKBOX). Both orders x
    `--repeats` so JIT warmup and page-cache drift can't masquerade as
    recorder cost. The headline is grad-steps/s recorder-on over
    recorder-off — forensics must ride along for free (>= 0.95 on the
    full lane). A functional dump round-trip (record -> dump -> parse)
    rides in the same artifact, because a HEALTHY A/B run never
    crashes and so never exercises the path the recorder exists for;
    the lane also asserts the healthy runs left no dump behind (the
    atexit hook is uninstalled by ``obs.close()``)."""
    import glob
    import tempfile

    from ape_x_dqn_tpu.configs import (EnvConfig, LearnerConfig,
                                       NetworkConfig, ObsConfig,
                                       ReplayConfig, get_config)
    from ape_x_dqn_tpu.obs.blackbox import FlightRecorder
    from ape_x_dqn_tpu.runtime.single_process import train_single_process
    from ape_x_dqn_tpu.utils.metrics import Metrics

    frames = int(args.bb_frames)
    repeats = max(int(args.repeats), 1)
    bb_dir = tempfile.mkdtemp(prefix="blackbox_ab_")

    def one_arm(blackbox_on: bool) -> float:
        cfg = get_config("pong").replace(
            env=EnvConfig(id="catch", kind="synthetic_atari"),
            network=NetworkConfig(kind="nature_cnn", dueling=True,
                                  compute_dtype="float32"),
            replay=ReplayConfig(kind="prioritized", capacity=2048,
                                min_fill=300),
            learner=LearnerConfig(batch_size=16, n_step=3,
                                  target_sync_every=16, sample_chunk=2),
            obs=ObsConfig(enabled=True, publish_every_steps=50,
                          heartbeat_timeout_s=120.0,
                          blackbox=blackbox_on, blackbox_dir=bb_dir))
        metrics = Metrics()  # in-memory: no JSONL I/O in the timed arm
        t0 = time.monotonic()
        out = train_single_process(cfg, total_env_frames=frames,
                                   metrics=metrics, train_every=2)
        wall = time.monotonic() - t0
        return out["grad_steps"] / wall if wall > 0 else 0.0

    on_runs: list[float] = []
    off_runs: list[float] = []
    for order in ("off_first", "on_first"):
        arms = (False, True) if order == "off_first" else (True, False)
        for arm_on in arms:
            for _ in range(repeats):
                rate = one_arm(arm_on)
                (on_runs if arm_on else off_runs).append(rate)
                log(f"blackbox A/B [{order}] recorder="
                    f"{'on' if arm_on else 'off'}: {rate:.4g} "
                    f"grad-steps/s")
    # healthy runs must leave NO dump: the crash hooks were installed
    # and then uninstalled by obs.close() before process exit
    stray = sorted(os.path.basename(p) for p in
                   glob.glob(os.path.join(bb_dir, "blackbox-*.json")))
    # functional round-trip: prove the dump path works here rather
    # than trusting it to the next real crash
    class _Sink:  # minimal obs facade (the bench has no Obs)
        def __init__(self):
            self.ctr: dict[str, int] = {}

        def count(self, name, n=1):
            self.ctr[name] = self.ctr.get(name, 0) + n

    sink = _Sink()
    rec = FlightRecorder(sink, peer="bench-bb", out_dir=bb_dir)
    rec.record("publish", step=1)
    dump_path = rec.dump("bench_roundtrip", component="bench")
    dump_ok = False
    if dump_path:
        try:
            with open(dump_path) as fh:
                doc = json.load(fh)
            dump_ok = (doc.get("blackbox") == 1
                       and doc.get("peer") == "bench-bb"
                       and len(doc.get("records", [])) == 1
                       and sink.ctr.get("blackbox_dumps", 0) == 1)
        except (OSError, json.JSONDecodeError):
            dump_ok = False
    med_on = spread(on_runs)["median"]
    med_off = spread(off_runs)["median"]
    ratio = round(med_on / med_off, 4) if med_off > 0 else 0.0
    result = {
        "metric": "blackbox_gradsteps_ratio",
        "value": ratio,
        "unit": "frac",
        "frames": frames,
        "on_grad_steps_per_s": spread(on_runs),
        "off_grad_steps_per_s": spread(off_runs),
        "dump_roundtrip_ok": dump_ok,
        "healthy_runs_left_no_dump": not stray,
        "stray_dumps": stray,
    }
    log(f"blackbox A/B: recorder-on {spread(on_runs)} vs off "
        f"{spread(off_runs)} grad-steps/s (ratio {ratio}), dump "
        f"round-trip {'ok' if dump_ok else 'FAILED'}, stray dumps "
        f"{stray or 'none'}")
    line = json.dumps(result)
    rc = 0
    if not dump_ok:
        log("blackbox gate FAIL: dump round-trip did not produce a "
            "parseable blackbox-<peer>.json")
        rc = 1
    if stray:
        log(f"blackbox gate FAIL: healthy A/B runs left dump(s) "
            f"behind: {stray}")
        rc = rc or 1
    gated = getattr(args, "perf_gate", False)
    if gated:
        args._baseline = _load_blackbox_baseline(args.smoke, frames)
        rc = rc or _gate_exit(result, args)
    if not args.smoke and ratio < _BLACKBOX_RATIO_FLOOR:
        log(f"blackbox gate FAIL: on/off ratio {ratio} below the "
            f"acceptance floor {_BLACKBOX_RATIO_FLOOR}")
        rc = rc or 1
    if rc == 0:
        path = _blackbox_artifact_path(args.smoke)
        try:
            with open(path, "w") as fh:
                fh.write(line + "\n")
        except OSError as e:
            log(f"could not write blackbox artifact {path}: {e!r}")
    else:
        log("blackbox gate: artifact of record NOT updated by this "
            "failing run")
    print(line, flush=True)
    raise SystemExit(rc)


def wire_codec_summary() -> dict:
    """Cheap in-memory codec ratio on the Atari-like synthetic frames —
    recorded in every default bench run so BENCH artifacts carry the
    wire ratio without the full --wire-ab socket harness."""
    from ape_x_dqn_tpu.comm.socket_transport import encode_batch

    msgs = _wire_ab_messages(2)
    raw = sum(len(encode_batch(m, "raw")) for m in msgs)
    comp = sum(len(encode_batch(m, "delta-deflate")) for m in msgs)
    transitions = len(msgs) * 8 * 12
    return {"ratio": round(raw / comp, 2),
            "raw_bytes_per_transition": round(raw / transitions, 1),
            "codec_bytes_per_transition": round(comp / transitions, 1)}


def _telemetry_soak(telemetry: bool, msgs: list[dict], iters: int,
                    pump_interval_s: float = 0.05) -> dict:
    """One arm of the telemetry A/B: ship the message list over a real
    loopback socket pair `iters` times with the fleet telemetry plane
    either fully ON (StampingTransport + TelemetryEmitter on the
    client, FleetAggregator merging frames on the server) or fully OFF
    (plain transport, capability not even offered), and measure
    experience items/s plus the telemetry side-channel's own rate."""
    import threading

    from ape_x_dqn_tpu.comm.socket_transport import (
        SocketIngestServer, SocketTransport)
    from ape_x_dqn_tpu.configs import ObsConfig
    from ape_x_dqn_tpu.obs.core import build_obs
    from ape_x_dqn_tpu.obs.fleet import (
        FleetAggregator, StampingTransport, TelemetryEmitter)
    from ape_x_dqn_tpu.utils.metrics import Metrics

    n_wire = int(msgs[0]["priorities"].shape[0])
    b = int(msgs[0]["priorities"].shape[1])
    total_units = len(msgs) * iters * n_wire
    srv = SocketIngestServer("127.0.0.1", 0)
    client = SocketTransport("127.0.0.1", srv.port, telemetry=telemetry)
    learner_obs = actor_obs = emitter = None
    tr = client
    if telemetry:
        learner_obs = build_obs(
            ObsConfig(enabled=True, heartbeat_timeout_s=0.0), Metrics())
        FleetAggregator(learner_obs).install(srv)
        actor_obs = build_obs(
            ObsConfig(enabled=True, heartbeat_timeout_s=0.0), Metrics())
        actor_obs.beat("actor-0", "bench")
        tr = StampingTransport(client, "bench-peer")
        emitter = TelemetryEmitter(tr, actor_obs, "bench-peer",
                                   interval_s=pump_interval_s)
    got = {"units": 0}
    done = threading.Event()

    def consume() -> None:
        while got["units"] < total_units:
            m = srv.recv_experience(timeout=10)
            if m is None:
                break
            got["units"] += m.rows
        done.set()

    thread = threading.Thread(target=consume, daemon=True)
    t0 = time.monotonic()
    thread.start()
    if emitter is not None:
        emitter.start()
    for _ in range(iters):
        for batch in msgs:
            tr.send_experience(batch)
    done.wait(timeout=60)
    if emitter is not None:
        emitter.stop()
    dt = time.monotonic() - t0
    out = {
        "items_per_s": total_units * b / dt,
        "telemetry_frames_per_s": srv.telemetry_frames / dt,
        "telemetry_bytes_per_s": srv.telemetry_bytes_in / dt,
    }
    client.close()
    srv.stop()
    if actor_obs is not None:
        actor_obs.close()
    if learner_obs is not None:
        learner_obs.close()
    assert got["units"] == total_units, \
        f"consumer saw {got['units']}/{total_units} units"
    return out


def bench_telemetry_ab(args, repeats: int | None = None,
                       n_msgs: int = 4, iters: int = 6) -> dict:
    """A/B the fleet telemetry plane's cost on the experience path it
    piggybacks on (obs/fleet.py): items/s with telemetry fully on
    (batch stamping + frame pump + learner-side aggregation) vs fully
    off, both orders on fresh socket pairs, median-of-`repeats` per
    arm. The plane is designed to be a rounding error here — a compact
    JSON frame every couple of seconds riding a link that carries MBs
    of frames — so the adoption bar is overhead within the run-to-run
    noise band, and this records the receipt."""
    repeats = args.repeats if repeats is None else repeats
    msgs = _wire_ab_messages(n_msgs)
    out: dict = {"units_per_msg": int(msgs[0]["priorities"].shape[0])}
    overheads = []
    for order in ("off_first", "on_first"):
        arms = (False, True) if order == "off_first" else (True, False)
        runs: dict[bool, list] = {False: [], True: []}
        last: dict[bool, dict] = {}
        for _ in range(repeats):
            for tel in arms:
                r = _telemetry_soak(tel, msgs, iters)
                runs[tel].append(r["items_per_s"])
                last[tel] = r
        overhead = 100.0 * (1.0 - spread(runs[True])["median"]
                            / spread(runs[False])["median"])
        overheads.append(overhead)
        out[order] = {
            "off_items_per_s": spread(runs[False]),
            "on_items_per_s": spread(runs[True]),
            "frames_per_s": round(last[True]["telemetry_frames_per_s"], 1),
            "bytes_per_s": round(last[True]["telemetry_bytes_per_s"]),
            "overhead_pct": round(overhead, 1),
        }
        log(f"telemetry A/B [{order}]: off {spread(runs[False])} vs on "
            f"{spread(runs[True])} items/s -> overhead "
            f"{overhead:+.1f}% (frames "
            f"{out[order]['frames_per_s']}/s, "
            f"{out[order]['bytes_per_s']} B/s)")
    out["overhead_pct"] = [round(x, 1) for x in overheads]
    return out


def telemetry_summary(args) -> dict:
    """Cheap single-pass telemetry overhead receipt recorded in every
    default bench run (one off arm + one on arm on a fresh socket
    pair): frames/s + bytes/s of the side-channel and the items/s
    overhead it cost. The full --telemetry-ab harness is the
    both-orders, median-of-repeats version of this number."""
    off = _telemetry_soak(False, _wire_ab_messages(2), 4)
    on = _telemetry_soak(True, _wire_ab_messages(2), 4)
    return {
        "frames_per_s": round(on["telemetry_frames_per_s"], 1),
        "bytes_per_s": round(on["telemetry_bytes_per_s"]),
        "overhead_pct": round(
            100.0 * (1.0 - on["items_per_s"] / off["items_per_s"]), 1),
    }


def bench_h2d(mb: int = 64, repeats: int = 3, iters: int = 4) -> list[float]:
    """Raw host->device link bandwidth: pure `device_put` MB/s of a
    pinned 64MB buffer, no compute. An ingest items/s trend cannot be
    read without the link's own rate at capture time — this number
    separates op cost from link state in every artifact."""
    buf = np.random.default_rng(7).integers(
        0, 255, mb * 1024 * 1024, dtype=np.uint8)
    jax.block_until_ready(jax.device_put(buf))  # warm the path
    rates = []
    for _ in range(repeats):
        t0 = time.monotonic()
        for _ in range(iters):
            out = jax.device_put(buf)
            jax.block_until_ready(out)
        rates.append(mb * iters / (time.monotonic() - t0))
    return rates


def bench_inference(net, spec, batch: int = 64, iters: int = 50,
                    repeats: int = 3) -> list[float]:
    """Forwards/s of the inference-server jit at its typical bucket size."""
    params = net.init(jax.random.key(0), jnp.zeros((1, *spec.obs_shape),
                                                   jnp.uint8))
    fwd = jax.jit(net.apply)
    obs = jnp.zeros((batch, *spec.obs_shape), jnp.uint8)
    jax.block_until_ready(fwd(params, obs))
    rates = []
    for _ in range(repeats):
        t0 = time.monotonic()
        for _ in range(iters):
            out = fwd(params, obs)
        jax.block_until_ready(out)
        rates.append(batch * iters / (time.monotonic() - t0))
    return rates


# -- multichip scaling lane (ISSUE 9) --------------------------------------

_MULTICHIP_ROUND = "r02"
_MULTICHIP_MARKER = "MULTICHIP_CHILD "


def _multichip_artifact_path(smoke: bool) -> str:
    """Artifact of record for the dp-scaling lane. Same smoke/full split
    as the main bench: a full-shape curve is never gated against a CI
    smoke curve."""
    here = os.path.dirname(os.path.abspath(__file__))
    name = ("MULTICHIP_SMOKE.json" if smoke
            else f"MULTICHIP_{_MULTICHIP_ROUND}.json")
    return os.path.join(here, name)


def _multichip_jsonl_path(smoke: bool) -> str:
    """Obs-format metrics JSONL the lane writes alongside the artifact —
    the file `python -m ape_x_dqn_tpu.obs.report` renders the multichip
    section from (per-dp multichip/dp<N>/* records + the summary
    gauges)."""
    return _multichip_artifact_path(smoke).replace(".json", ".jsonl")


def _load_multichip_baseline(smoke: bool, virtual: bool,
                             dp_list: list[int]
                             ) -> tuple[str | None, dict | None]:
    """Newest COMPARABLE multichip artifact: same smoke class, same
    virtual-vs-real device mode, same dp set. Scaling efficiency on 8
    virtual devices sharing one host says nothing about 8 real chips
    (and vice versa), and a dp=1,2 smoke curve says nothing about the
    full 1/2/4/8 sweep — cross-shape comparisons would gate on noise.
    Artifacts that are not curves (a raw capture with no metric/value)
    are skipped the same way _load_baseline skips null driver
    captures."""
    import glob
    here = os.path.dirname(os.path.abspath(__file__))
    if smoke:
        cands = [os.path.join(here, "MULTICHIP_SMOKE.json")]
    else:
        cands = [p for p in glob.glob(os.path.join(here,
                                                   "MULTICHIP_*.json"))
                 if os.path.basename(p) != "MULTICHIP_SMOKE.json"]
    cands = sorted((p for p in cands if os.path.exists(p)),
                   key=os.path.getmtime, reverse=True)
    for path in cands:
        try:
            with open(path) as fh:
                doc = json.load(fh)
        except (OSError, json.JSONDecodeError):
            continue
        if not (isinstance(doc, dict) and "metric" in doc
                and "value" in doc):
            continue
        if bool(doc.get("virtual_devices")) != bool(virtual):
            log(f"multichip gate: {os.path.basename(path)} is a "
                f"{'virtual' if doc.get('virtual_devices') else 'real'}"
                f"-device curve — not comparable, skipped")
            continue
        if sorted(doc.get("dp") or []) != sorted(dp_list):
            log(f"multichip gate: {os.path.basename(path)} covers "
                f"dp={doc.get('dp')} != {dp_list} — not comparable, "
                f"skipped")
            continue
        return path, doc
    return None, None


class _GaugeSink:
    """Minimal obs stand-in for StageProfiler/publish_multichip in the
    bench child: collects the literal gauge emissions into a dict."""

    def __init__(self):
        self.gauges: dict[str, float] = {}

    def gauge(self, name: str, value) -> None:
        self.gauges[name] = float(value)


def _dist_seg_chunk(replay, spec, dp: int, g: int, rng):
    """[dp, g]-stacked frame-ring segments for the lockstep add path
    (items {"seg_frames": [dp, g, F, H, W], fields [dp, g, B]} —
    dist_learner.add -> FrameRingReplay.add_lockstep)."""
    b, f = replay.B, replay.F
    items = {
        "seg_frames": jnp.asarray(
            rng.integers(0, 255, (dp, g, f, *spec.obs_shape[:2])),
            jnp.uint8),
        "action": jnp.asarray(
            rng.integers(0, spec.num_actions, (dp, g, b)), jnp.int32),
        "reward": jnp.asarray(rng.normal(size=(dp, g, b)), jnp.float32),
        "discount": jnp.full((dp, g, b), 0.99**3, jnp.float32),
        "next_off": jnp.full((dp, g, b), 3, jnp.int32),
    }
    return items, jnp.asarray(rng.uniform(0.1, 2.0, (dp, g, b)),
                              jnp.float32)


def bench_multichip_child(args) -> None:
    """One dp point of the scaling sweep, run in a FRESH process: the
    only process of the sweep that touches a JAX backend while it
    lives (for a virtual sweep the parent provisions
    JAX_PLATFORMS/XLA_FLAGS before this interpreter imports jax — the
    flag is read once at backend init).

    Builds the dp-sharded frame-ring stack the dist driver runs
    (FrameRingReplay at per-shard capacity under the dist learner on a
    (dp, 1) mesh), prefills via timed lockstep add dispatches, times
    the fused train_many, and attributes it through StageProfiler's
    "train_dist" stage — the same roofline math the live driver
    publishes. Emits ONE marker-prefixed JSON line on stdout."""
    from ape_x_dqn_tpu.configs import (LearnerConfig, NetworkConfig,
                                       RunConfig)
    from ape_x_dqn_tpu.envs.base import EnvSpec
    from ape_x_dqn_tpu.models import build_network
    from ape_x_dqn_tpu.obs.profiling import StageProfiler
    from ape_x_dqn_tpu.parallel.mesh import make_mesh
    from ape_x_dqn_tpu.replay.frame_ring import FrameRingReplay
    from ape_x_dqn_tpu.runtime.family import build_learner as family_learner
    from ape_x_dqn_tpu.utils.rng import component_key

    dp, n_want = (int(v) for v in args.multichip_child.split("/"))
    devices = jax.devices()
    log(f"multichip child dp={dp}: {len(devices)} "
        f"{devices[0].platform} devices")
    if len(devices) < n_want:
        raise SystemExit(
            f"multichip child dp={dp}: the sweep needs {n_want} devices "
            f"but JAX found {len(devices)} ({devices[0].platform}); for "
            f"virtual host devices ask by name: --multichip virtual:...")
    mesh = make_mesh(dp=dp, tp=1)
    spec = EnvSpec(obs_shape=(84, 84, 4), obs_dtype=np.dtype(np.uint8),
                   discrete=True, num_actions=18)
    seg = 16
    # equal-total-capacity split: per-shard capacity shrinks with dp
    # (the whole point of sharding), floored to a legal segment multiple
    cap_shard = max((args.capacity // dp) // seg, 4) * seg
    replay = FrameRingReplay(capacity=cap_shard, seg_transitions=seg,
                             n_step=3, obs_shape=spec.obs_shape)
    ncfg = NetworkConfig(kind="nature_cnn", dueling=True)
    net = build_network(ncfg, spec)
    params = net.init(component_key(0, "net_init"),
                      jnp.zeros((1, 84, 84, 4), jnp.uint8))
    lcfg = LearnerConfig(batch_size=args.batch_size,
                         sample_chunk=args.sample_chunk)
    learner = family_learner(RunConfig(network=ncfg, learner=lcfg), net,
                             replay, mesh)
    state = learner.init(params, None, component_key(0, "learner"))

    # -- timed lockstep ingest (equal [dp, g] blocks, like the driver's
    # round-robin split ships them) -----------------------------------
    rng = np.random.default_rng(0)
    segs_per_shard = max(args.prefill // (dp * seg), 1)
    g = min(segs_per_shard, 8)
    items, pris = _dist_seg_chunk(replay, spec, dp, g, rng)
    state = learner.add(state, items, pris)  # compile
    jax.block_until_ready(state.replay.tree)
    n_dispatch = max(segs_per_shard // g, 1)
    t0 = time.monotonic()
    for _ in range(n_dispatch):
        state = learner.add(state, items, pris)
    jax.block_until_ready(state.replay.tree)
    rows_per_s = n_dispatch * dp * g * seg / (time.monotonic() - t0)
    log(f"lockstep ingest: {rows_per_s:,.0f} rows/s "
        f"({n_dispatch} dispatches of [dp={dp}, g={g}] blocks)")

    # -- fused train_many, attributed as "train_dist" ------------------
    sink = _GaugeSink()
    profiler = StageProfiler(sink)
    steps = args.steps_per_dispatch
    try:
        compiled = type(learner).train_many.lower(learner, state,
                                                  steps).compile()
    except Exception as e:  # noqa: BLE001 - attribution is best-effort
        log(f"multichip child: AOT cost analysis unavailable: {e!r}")
        compiled = None
    profiler.attach("train_dist", steps, compiled=compiled)
    t0 = time.monotonic()
    state, m = learner.train_many(state, steps)
    jax.block_until_ready(m["loss"])
    log(f"train_many compile+first dispatch: "
        f"{time.monotonic() - t0:.1f}s (loss={float(m['loss']):.4f})")
    rates = []
    for _ in range(args.repeats):
        t0 = time.monotonic()
        for _ in range(args.dispatches):
            with profiler.window("train_dist", steps):
                state, m = learner.train_many(state, steps)
                jax.block_until_ready(m["loss"])
        rates.append(steps * args.dispatches / (time.monotonic() - t0))
    assert np.isfinite(float(m["loss"])), "non-finite loss at dp=%d" % dp
    result = {
        "dp": dp,
        "grad_steps_per_s": spread(rates),
        "ingest_rows_per_s": float(f"{rows_per_s:.4g}"),
        "gauges": sink.gauges,
        "shards": learner.shard_stats(state),
        "cap_shard": cap_shard,
        "batch_size": args.batch_size,
        "n_devices": len(devices),
        "platform": devices[0].platform,
    }
    print(_MULTICHIP_MARKER + json.dumps(result), flush=True)


def bench_multichip(args) -> None:
    """The dp-scaling sweep (tentpole (b)): one child process per dp
    point, each seeing the same CONSTANT device count, so every point
    runs on the same backend topology and the efficiency curve isolates
    sharding/collective overhead from device-count skew.

    `--multichip dp=1,2,4` runs on the machine's real devices; a child
    that finds fewer than max(dp) of them fails. `--multichip
    virtual:dp=1,2,4` asks by name for that many virtual CPU host
    devices instead (an overhead signal, never a speedup claim).
    This parent never initialises a JAX backend: a chip belongs to one
    process at a time, and a parent holding them would leave every
    child hung.

    Writes the curve artifact (MULTICHIP_<round>.json, smoke runs to
    MULTICHIP_SMOKE.json) plus an obs-format metrics JSONL that
    `python -m ape_x_dqn_tpu.obs.report` renders as the multichip
    section. Under --perf-gate the headline (scaling efficiency at the
    largest dp) gates against the newest comparable artifact — same
    virtual/real mode, same dp set, same smoke class — with the same
    anti-ratchet rule as the main bench (a failing run never becomes
    the next baseline)."""
    import subprocess

    spec_str = args.multichip.strip()
    virtual = spec_str.startswith("virtual:")
    if virtual:
        spec_str = spec_str[len("virtual:"):]
    if spec_str.startswith("dp="):
        spec_str = spec_str[3:]
    try:
        dp_list = sorted({int(d) for d in spec_str.split(",") if d})
    except ValueError:
        raise SystemExit(
            f"bad --multichip dp list: {args.multichip!r}") from None
    if not dp_list or dp_list[0] < 1:
        raise SystemExit(f"bad --multichip dp list: {args.multichip!r}")
    bad = [d for d in dp_list if args.batch_size % d]
    if bad:
        raise SystemExit(f"--batch-size {args.batch_size} must divide "
                         f"by every dp point (violates: {bad})")
    n_dev = max(dp_list)
    env = os.environ.copy()
    # the forcing flag is read ONCE at backend init — hence child
    # processes. Any inherited copy is stripped: only a sweep that asked
    # for virtual devices by name gets them, so a CPU host can never
    # pass for a multi-device one
    xf = " ".join(
        t for t in env.get("XLA_FLAGS", "").split()
        if not t.startswith("--xla_force_host_platform_device_count"))
    if virtual:
        xf = f"{xf} --xla_force_host_platform_device_count={n_dev}"
        env["JAX_PLATFORMS"] = "cpu"
        log(f"multichip: {n_dev} VIRTUAL host devices (one shared "
            f"host — efficiency is an overhead signal, not a speedup "
            f"claim; PERF.md 'Multi-chip scaling')")
    else:
        log(f"multichip: every child must find {n_dev} real devices")
    env["XLA_FLAGS"] = xf.strip()
    curve: dict[str, dict] = {}
    ok = True
    for dp in dp_list:
        cmd = [sys.executable, os.path.abspath(__file__),
               "--multichip-child", f"{dp}/{n_dev}",
               "--capacity", str(args.capacity),
               "--batch-size", str(args.batch_size),
               "--prefill", str(args.prefill),
               "--steps-per-dispatch", str(args.steps_per_dispatch),
               "--dispatches", str(args.dispatches),
               "--repeats", str(args.repeats),
               "--sample-chunk", str(args.sample_chunk)]
        if args.smoke:
            cmd.append("--smoke")
        t0 = time.monotonic()
        try:
            proc = subprocess.run(cmd, env=env, capture_output=True,
                                  text=True, timeout=1800)
        except subprocess.TimeoutExpired:
            log(f"multichip dp={dp}: child TIMED OUT")
            ok = False
            continue
        point = None
        for line in proc.stdout.splitlines():
            if line.startswith(_MULTICHIP_MARKER):
                try:
                    point = json.loads(line[len(_MULTICHIP_MARKER):])
                except json.JSONDecodeError:
                    point = None
        if proc.returncode != 0 or point is None:
            tail = (proc.stderr or proc.stdout or "").strip()
            log(f"multichip dp={dp}: child FAILED rc={proc.returncode}"
                f"\n{tail[-2000:]}")
            ok = False
            continue
        point["wall_s"] = round(time.monotonic() - t0, 1)
        curve[str(dp)] = point
        log(f"multichip dp={dp}: "
            f"{point['grad_steps_per_s']['median']} grad-steps/s, "
            f"shard fill {point['shards']['fill_min']:.3f}.."
            f"{point['shards']['fill_max']:.3f} ({point['wall_s']}s)")
    # scaling efficiency vs dp=1: rate_dp / (dp * rate_dp1). 1.0 is
    # linear scaling; virtual devices contend for one host, so < 1 is
    # expected there and the number reads as overhead, not speedup
    base = curve.get("1", {}).get("grad_steps_per_s", {}).get("median")
    for dp in dp_list:
        pt = curve.get(str(dp))
        if pt is None:
            continue
        rate = pt["grad_steps_per_s"]["median"]
        pt["efficiency"] = (round(rate / (dp * base), 4)
                            if base else None)
    eff_points = [curve[str(d)]["efficiency"] for d in dp_list
                  if str(d) in curve
                  and curve[str(d)].get("efficiency") is not None]
    headline = eff_points[-1] if eff_points else 0.0
    ok = ok and len(curve) == len(dp_list) and bool(eff_points)

    jsonl_path = _multichip_jsonl_path(args.smoke)
    try:
        with open(jsonl_path, "w") as fh:
            for i, dp in enumerate(dp_list):
                pt = curve.get(str(dp))
                if pt is None:
                    continue
                rec = {"step": i,
                       f"multichip/dp{dp}/grad_steps_per_s":
                           pt["grad_steps_per_s"]["median"],
                       f"multichip/dp{dp}/efficiency":
                           pt.get("efficiency"),
                       f"multichip/dp{dp}/shard_fill_min":
                           pt["shards"]["fill_min"],
                       f"multichip/dp{dp}/shard_fill_max":
                           pt["shards"]["fill_max"],
                       f"multichip/dp{dp}/ingest_rows_per_s":
                           pt["ingest_rows_per_s"]}
                for k in ("mfu_train_dist", "device_ms_train_dist",
                          "hbm_bw_frac_train_dist"):
                    if k in pt["gauges"]:
                        rec[f"multichip/dp{dp}/{k}"] = pt["gauges"][k]
                fh.write(json.dumps(rec) + "\n")
            # summary record: last-write-wins gauges for the SLO table
            # (largest completed dp point) + the virtual-device stamp
            last = curve.get(str(dp_list[-1])) or {}
            summary_rec = {"step": len(dp_list),
                           "virtual_devices": virtual,
                           "gauge/dp_scaling_efficiency": headline}
            if last:
                summary_rec["gauge/replay_shard_fill_min"] = \
                    last["shards"]["fill_min"]
                summary_rec["gauge/replay_shard_fill_max"] = \
                    last["shards"]["fill_max"]
                for k, v in last["gauges"].items():
                    summary_rec[f"gauge/{k}"] = v
            fh.write(json.dumps(summary_rec) + "\n")
        log(f"multichip metrics JSONL -> {jsonl_path} (render with "
            f"`python -m ape_x_dqn_tpu.obs.report {jsonl_path}`)")
    except OSError as e:
        log(f"could not write multichip metrics JSONL: {e!r}")

    result = {
        "metric": "multichip_dp_scaling_efficiency",
        "value": headline,
        "unit": "ratio",
        "ok": ok,
        "virtual_devices": virtual,
        "dp": dp_list,
        "n_devices": n_dev,
        "smoke": bool(args.smoke),
        "curve": curve,
        "metrics_jsonl": os.path.basename(jsonl_path),
    }
    line = json.dumps(result)
    gated = getattr(args, "perf_gate", False)
    rc = 0
    if gated:
        args._baseline = _load_multichip_baseline(args.smoke, virtual,
                                                  dp_list)
        rc = _gate_exit(result, args)
    if not ok:
        log("multichip: sweep incomplete — artifact NOT updated")
        rc = rc or 1
    if rc == 0 or not gated:
        if ok:
            path = _multichip_artifact_path(args.smoke)
            try:
                with open(path, "w") as fh:
                    fh.write(line + "\n")
            except OSError as e:
                log(f"could not write multichip artifact {path}: {e!r}")
    else:
        log("multichip perf-gate: artifact of record NOT updated by "
            "this failing run")
    print(line, flush=True)
    raise SystemExit(rc)


# -- tiered replay lane (replay/cold_store.py; ROADMAP item 3) ---------------


def _tiered_artifact_path(smoke: bool) -> str:
    """Artifact of record for the tiered-replay lane. Same smoke/full
    split as the main bench: a CI smoke run only ever gates against a
    smoke baseline."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(here, "TIERED_SMOKE.json" if smoke
                        else "TIERED_LATEST.json")


def _load_tiered_baseline(smoke: bool, storage: str, capacity: int
                          ) -> tuple[str | None, dict | None]:
    """Newest COMPARABLE tiered artifact: same smoke class, same
    storage layout, same ring capacity. The on-arm grad-steps/s bakes
    in the eviction-block geometry those fix — a cross-shape gate
    would fire on a shape change, not a regression."""
    path = _tiered_artifact_path(smoke)
    try:
        with open(path) as fh:
            doc = json.load(fh)
    except (OSError, json.JSONDecodeError):
        return None, None
    if not (isinstance(doc, dict) and "metric" in doc
            and "value" in doc):
        return None, None
    if (doc.get("storage") != storage
            or doc.get("capacity") != capacity):
        log(f"tiered gate: {os.path.basename(path)} is "
            f"{doc.get('storage')}@{doc.get('capacity')}, this run is "
            f"{storage}@{capacity} — not comparable, skipped")
        return None, None
    return path, doc


def _tiered_seg_chunk(replay, spec, g: int, rng) -> tuple[dict, object]:
    """Delta-compressible frame segments for the tiered lane:
    consecutive frames share a base image with sparse per-frame noise,
    like real emulator play. Pure-random frames (what _seg_chunk
    generates) are incompressible by construction and would make the
    lane's bytes/transition bar unmeetable regardless of codec
    quality — the cold pack exists to exploit frame redundancy, so the
    synthetic stream has to carry some."""
    b, f = replay.B, replay.F
    h, w = spec.obs_shape[:2]
    base = rng.integers(0, 255, (h, w)).astype(np.uint8)
    frames = np.broadcast_to(base, (g, f, h, w)).copy()
    noise = frames[:, :, ::7, ::11]
    frames[:, :, ::7, ::11] = rng.integers(0, 255, noise.shape)
    items = {
        "seg_frames": np.ascontiguousarray(frames),
        "action": np.ascontiguousarray(
            rng.integers(0, spec.num_actions, (g, b)).astype(np.int32)),
        "reward": np.ascontiguousarray(
            rng.normal(size=(g, b)).astype(np.float32)),
        "discount": np.full((g, b), 0.99**3, np.float32),
        "next_off": np.full((g, b), 3, np.int32),
    }
    pris = np.ascontiguousarray(
        rng.uniform(0.1, 2.0, (g, b)).astype(np.float32))
    return items, pris


def _tiered_flat_chunk(spec, chunk: int, rng) -> tuple[dict, object]:
    """Flat-layout analog of _tiered_seg_chunk. cold_plan's delta rows
    for a stacked obs are IMAGE rows, so compressibility needs
    row-coherent images: a row-constant base plus sparse noise."""
    h = spec.obs_shape[0]
    base = np.broadcast_to(
        rng.integers(0, 255, spec.obs_shape[1:]).astype(np.uint8),
        spec.obs_shape)

    def obs_block():
        o = np.broadcast_to(base, (chunk, *spec.obs_shape)).copy()
        noise = o[:, ::7, ::11]
        o[:, ::7, ::11] = rng.integers(0, 255, noise.shape)
        return np.ascontiguousarray(o)

    items = {
        "obs": obs_block(),
        "action": np.ascontiguousarray(
            rng.integers(0, spec.num_actions, chunk).astype(np.int32)),
        "reward": np.ascontiguousarray(
            rng.normal(size=chunk).astype(np.float32)),
        "next_obs": obs_block(),
        "discount": np.full(chunk, 0.99**3, np.float32),
    }
    pris = np.ascontiguousarray(
        rng.uniform(0.1, 2.0, chunk).astype(np.float32))
    return items, pris


def bench_tiered_ab(args) -> None:
    """Tiered-replay A/B (ROADMAP item 3): grad-steps/s with every
    ingest block riding the ring-full eviction swap — jitted
    evict_plan/read_region picks and reads the ring's lowest-priority-
    mass region, the region is fetched to host and compressed into the
    ColdStore, and the fresh block overwrites it via the directed
    add_at — vs the plain FIFO add path at identical shapes. Then a
    capacity soak (the cold tier must hold --tiered-ring-mult x the
    ring's transitions at under 1/8 of its bytes/transition) and a
    recall decompress-throughput measurement.

    This is the driver's _ship_staged_cold/_cold_refill_tick data path
    run open-loop at the learner API, so the A/B isolates the swap
    cost itself (no actor fleet, no stager jitter). Artifact:
    TIERED_LATEST.json (TIERED_SMOKE.json under --smoke); --perf-gate
    gates gsps_on against the newest comparable artifact with the
    anti-ratchet rule (a failing run never becomes the baseline)."""
    from ape_x_dqn_tpu.replay.cold_store import ColdStore, codec_status
    from ape_x_dqn_tpu.replay.frame_ring import frame_segment_spec
    from ape_x_dqn_tpu.runtime.learner import transition_item_spec

    capacity, batch, storage = args.capacity, args.batch_size, args.storage
    net, learner, state, spec = build_learner(capacity, batch, storage,
                                              args.sample_chunk)
    replay = learner.replay
    rng = np.random.default_rng(7)
    block_tr = max(min(args.tiered_block, capacity // 4), 1)
    if storage == "frame_ring":
        block_units = max(block_tr // replay.B, 1)
        block_tr = block_units * replay.B
        unit_items = replay.B
        item_spec = frame_segment_spec(replay.B, replay.n,
                                       spec.obs_shape, spec.obs_dtype)
        ptail = (replay.B,)
        host_items, host_pris = _tiered_seg_chunk(replay, spec,
                                                  block_units, rng)
    else:
        block_units = block_tr
        unit_items = 1
        item_spec = transition_item_spec(spec.obs_shape, spec.obs_dtype)
        ptail = ()
        host_items, host_pris = _tiered_flat_chunk(spec, block_tr, rng)
    cold_cap = args.tiered_cold_capacity or 16 * capacity
    cold = ColdStore(item_spec, cold_cap, unit_items=unit_items,
                     ptail=ptail, compress_level=1)
    log(f"tiered: codec {codec_status()[1]}, ring {capacity} "
        f"transitions ({storage}), cold capacity {cold_cap}, block "
        f"{block_tr} transitions ({block_units} staging units)")

    def put_block():
        # fresh h2d per dispatch in BOTH arms — real ingest always
        # lands from host staging memory, so the link cost is common
        # mode and the A/B isolates the swap machinery
        staged = {k: jax.device_put(v) for k, v in host_items.items()}
        return staged, jax.device_put(host_pris)

    # prefill the ring FULL through the real add jit (the tier only
    # engages on a full ring)
    for _ in range(max(capacity // block_tr, 1)):
        staged, pris = put_block()
        state = learner.add(state, staged, pris)
    jax.block_until_ready(state.replay.tree)

    # warm every graph either arm dispatches
    t0 = time.monotonic()
    state, m = learner.train_many(state, args.steps_per_dispatch)
    jax.block_until_ready(m["loss"])
    start, _ev_items, ev_pri = learner.evict_region(state, block_units)
    np.asarray(ev_pri)
    staged, pris = put_block()
    state = learner.add_at(state, staged, pris, start)
    jax.block_until_ready(state.replay.tree)
    log(f"tiered compile+warmup: {time.monotonic() - t0:.1f}s")

    def swap_once(state, store):
        """One eviction swap — the _ship_staged_cold body, open-loop
        (host fetch BEFORE the donated add_at, same as the driver)."""
        staged, pris = put_block()
        start, ev_items, ev_pri = learner.evict_region(state,
                                                       block_units)
        ev_host = {k: np.asarray(v) for k, v in ev_items.items()}
        ev_pri = np.asarray(ev_pri)
        state = learner.add_at(state, staged, pris, start)
        if store is not None:
            live = int((ev_pri > 0).sum())
            store.put(ev_host, ev_pri, live)
        return state

    # A/B: per dispatch, one ingest block + one train_many. OFF = the
    # plain FIFO add; ON = the full eviction swap.
    steps, dispatches = args.steps_per_dispatch, args.dispatches
    off_rates, on_rates = [], []
    for _ in range(args.repeats):
        t0 = time.monotonic()
        for _ in range(dispatches):
            staged, pris = put_block()
            state = learner.add(state, staged, pris)
            state, m = learner.train_many(state, steps)
        jax.block_until_ready(m["loss"])
        off_rates.append(steps * dispatches / (time.monotonic() - t0))
        t0 = time.monotonic()
        for _ in range(dispatches):
            state = swap_once(state, cold)
            state, m = learner.train_many(state, steps)
        jax.block_until_ready(m["loss"])
        on_rates.append(steps * dispatches / (time.monotonic() - t0))
    gsps_off = float(np.median(off_rates))
    gsps_on = float(np.median(on_rates))
    on_off = gsps_on / gsps_off if gsps_off else 0.0
    log(f"tiered A/B: off {spread(off_rates)} vs on {spread(on_rates)} "
        f"grad-steps/s (on/off {on_off:.3f})")

    # capacity soak: keep swapping until the cold tier holds the target
    # ring multiple of LIVE transitions; the swap bound is the honest
    # failure mode if the door starts dropping
    target = int(args.tiered_ring_mult * capacity)
    max_swaps = 4 * (target // block_tr + 1)
    swaps = 0
    t0 = time.monotonic()
    while cold.transitions < target and swaps < max_swaps:
        state = swap_once(state, cold)
        swaps += 1
    jax.block_until_ready(state.replay.tree)
    soak_s = time.monotonic() - t0
    evict_tr_per_s = swaps * block_tr / soak_s if soak_s else 0.0
    log(f"tiered soak: {swaps} swaps -> {cold.transitions} live cold "
        f"transitions in {soak_s:.1f}s ({evict_tr_per_s:,.0f} "
        f"transitions/s through the evict+compress path)")

    # stats snapshot BEFORE the recall measurement drains segments
    cold_tr = cold.transitions
    n_segments = len(cold)
    ratio = cold.compression_ratio()
    cold_bpt = (cold.bytes_compressed / cold_tr) if cold_tr \
        else float("inf")
    # the ring's resident device bytes per transition (storage + sum
    # tree + cursors — everything HBM pays for the hot set)
    ring_bytes = sum(getattr(leaf, "nbytes", 0)
                     for leaf in jax.tree.leaves(state.replay))
    ring_bpt = ring_bytes / capacity
    bytes_ratio = cold_bpt / ring_bpt if ring_bpt else float("inf")
    cold_ring_ratio = cold_tr / capacity

    rec_segments = min(n_segments, 32)
    rec_items = 0
    t0 = time.monotonic()
    for batch_out in cold.recall(rec_segments):
        rec_items += int(np.asarray(batch_out["priorities"]).size)
    rec_s = time.monotonic() - t0
    recall_items_per_s = rec_items / rec_s if rec_s else 0.0
    log(f"tiered recall: {rec_segments} segments, {rec_items} "
        f"transitions in {rec_s:.2f}s ({recall_items_per_s:,.0f} "
        f"items/s decompressed)")

    ok = (cold_ring_ratio >= args.tiered_ring_mult
          and bytes_ratio < 0.125)
    result = {
        "metric": "tiered_grad_steps_per_s_on",
        "value": float(f"{gsps_on:.4g}"),
        "unit": "steps/s",
        "ok": ok,
        "smoke": bool(args.smoke),
        "storage": storage,
        "capacity": capacity,
        "cold_capacity": cold_cap,
        "batch": batch,
        "block_transitions": block_tr,
        "codec": codec_status()[1],
        "grad_steps_per_s_off": spread(off_rates),
        "grad_steps_per_s_on": spread(on_rates),
        "on_off_frac": round(on_off, 4),
        "within_5pct": bool(on_off >= 0.95),
        "cold_transitions": cold_tr,
        "cold_segments": n_segments,
        "cold_ring_ratio": round(cold_ring_ratio, 3),
        "cold_bytes_per_transition": round(cold_bpt, 2),
        "ring_bytes_per_transition": round(ring_bpt, 2),
        "bytes_ratio": round(bytes_ratio, 5),
        "cold_compression_ratio": round(ratio, 2),
        "evict_transitions_per_s": round(evict_tr_per_s, 1),
        "recall_items_per_s": round(recall_items_per_s, 1),
        "door": {"stored": cold.stored, "dropped": cold.dropped,
                 "displaced": cold.displaced,
                 "recalled": cold.recalled},
    }
    line = json.dumps(result)
    gated = getattr(args, "perf_gate", False)
    rc = 0
    if gated:
        args._baseline = _load_tiered_baseline(args.smoke, storage,
                                               capacity)
        rc = _gate_exit(result, args)
    if not ok:
        log(f"tiered: capacity criteria NOT met (ring multiple "
            f"{cold_ring_ratio:.2f} vs >= {args.tiered_ring_mult}, "
            f"bytes ratio {bytes_ratio:.4f} vs < 0.125)")
        rc = rc or 1
    if rc == 0 or not gated:
        if ok:
            path = _tiered_artifact_path(args.smoke)
            try:
                with open(path, "w") as fh:
                    fh.write(line + "\n")
            except OSError as e:
                log(f"could not write tiered artifact {path}: {e!r}")
    else:
        log("tiered perf-gate: artifact of record NOT updated by this "
            "failing run")
    print(line, flush=True)
    raise SystemExit(rc)


def _tiered_disk_artifact_path(smoke: bool) -> str:
    """Artifact of record for the tiered lane's disk arm. Same
    smoke/full split as every other lane."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(here, "TIERED_DISK_SMOKE.json" if smoke
                        else "TIERED_DISK_LATEST.json")


def _load_tiered_disk_baseline(smoke: bool, storage: str, capacity: int,
                               cold_capacity: int
                               ) -> tuple[str | None, dict | None]:
    """Newest COMPARABLE disk-arm artifact: same smoke class, same
    storage layout, same ring AND cold capacities. The on-arm
    grad-steps/s bakes in both the eviction-block geometry and the
    spill pressure (cold capacity sets when the door starts handing
    segments to the writeback queue)."""
    path = _tiered_disk_artifact_path(smoke)
    try:
        with open(path) as fh:
            doc = json.load(fh)
    except (OSError, json.JSONDecodeError):
        return None, None
    if not (isinstance(doc, dict) and "metric" in doc
            and "value" in doc):
        return None, None
    if (doc.get("storage") != storage
            or doc.get("capacity") != capacity
            or doc.get("cold_capacity") != cold_capacity):
        log(f"tiered-disk gate: {os.path.basename(path)} is "
            f"{doc.get('storage')}@{doc.get('capacity')}/"
            f"{doc.get('cold_capacity')}, this run is "
            f"{storage}@{capacity}/{cold_capacity} — not comparable, "
            f"skipped")
        return None, None
    return path, doc


def bench_tiered_disk(args) -> None:
    """Disk arm of the tiered lane (--tiered-ab --tiered-disk, PR 16):
    grad-steps/s with every ingest block riding the eviction swap AND
    the cold store's admission-door losers spilling to the disk rung's
    async writeback (replay/disk_store.py) — vs the identical swap
    loop with the spill hook off. Both arms run with the cold store
    already AT capacity so the door (and hence the spill traffic) is
    live for every timed dispatch; the A/B therefore isolates exactly
    what the disk rung adds to the ship path, which by construction is
    one bounded put_nowait per door loser (queue_full counts refusals
    — the ship path never waits on disk).

    Then a retention soak: keep swapping on the spill-on store until
    the DISK holds --tiered-disk-mult x the cold tier's transitions
    (the 10^8-retention story at bench scale: ring << RAM cold <<
    disk), drain the writeback queue, and measure promote() readback
    throughput. Artifact: TIERED_DISK_LATEST.json
    (TIERED_DISK_SMOKE.json under --smoke); --perf-gate gates gsps_on
    against the newest comparable artifact with the anti-ratchet rule
    (a failing run never becomes the baseline)."""
    import shutil
    import tempfile

    from ape_x_dqn_tpu.replay.cold_store import ColdStore, codec_status
    from ape_x_dqn_tpu.replay.disk_store import DiskStore
    from ape_x_dqn_tpu.replay.frame_ring import frame_segment_spec
    from ape_x_dqn_tpu.runtime.learner import transition_item_spec

    capacity, batch, storage = args.capacity, args.batch_size, args.storage
    net, learner, state, spec = build_learner(capacity, batch, storage,
                                              args.sample_chunk)
    replay = learner.replay
    rng = np.random.default_rng(7)
    block_tr = max(min(args.tiered_block, capacity // 4), 1)
    if storage == "frame_ring":
        block_units = max(block_tr // replay.B, 1)
        block_tr = block_units * replay.B
        unit_items = replay.B
        item_spec = frame_segment_spec(replay.B, replay.n,
                                       spec.obs_shape, spec.obs_dtype)
        ptail = (replay.B,)
        host_items, host_pris = _tiered_seg_chunk(replay, spec,
                                                  block_units, rng)
    else:
        block_units = block_tr
        unit_items = 1
        item_spec = transition_item_spec(spec.obs_shape, spec.obs_dtype)
        ptail = ()
        host_items, host_pris = _tiered_flat_chunk(spec, block_tr, rng)
    # a SMALL cold tier relative to the soak target: the disk arm's
    # whole point is retention far beyond RAM, so the RAM rung here is
    # 2x the ring and the disk must end up holding
    # --tiered-disk-mult x that
    cold_cap = args.tiered_cold_capacity or 2 * capacity
    target = int(args.tiered_disk_mult * cold_cap)
    disk_cap = 2 * target  # headroom: the disk door must never gate
    #                        the retention criterion itself
    disk_dir = tempfile.mkdtemp(prefix="tiered_disk_")
    disk = DiskStore(disk_dir, disk_cap,
                     queue_depth=args.tiered_disk_queue)
    cold_off = ColdStore(item_spec, cold_cap, unit_items=unit_items,
                         ptail=ptail, compress_level=1)
    cold_on = ColdStore(item_spec, cold_cap, unit_items=unit_items,
                        ptail=ptail, compress_level=1, spill=disk)
    log(f"tiered-disk: codec {codec_status()[1]}, ring {capacity} "
        f"transitions ({storage}), cold {cold_cap}, disk capacity "
        f"{disk_cap} (target {target}), block {block_tr} transitions")

    def put_block():
        staged = {k: jax.device_put(v) for k, v in host_items.items()}
        return staged, jax.device_put(host_pris)

    for _ in range(max(capacity // block_tr, 1)):
        staged, pris = put_block()
        state = learner.add(state, staged, pris)
    jax.block_until_ready(state.replay.tree)

    t0 = time.monotonic()
    state, m = learner.train_many(state, args.steps_per_dispatch)
    jax.block_until_ready(m["loss"])
    start, _ev_items, ev_pri = learner.evict_region(state, block_units)
    np.asarray(ev_pri)
    staged, pris = put_block()
    state = learner.add_at(state, staged, pris, start)
    jax.block_until_ready(state.replay.tree)
    log(f"tiered-disk compile+warmup: {time.monotonic() - t0:.1f}s")

    def swap_once(state, store):
        staged, pris = put_block()
        start, ev_items, ev_pri = learner.evict_region(state,
                                                       block_units)
        ev_host = {k: np.asarray(v) for k, v in ev_items.items()}
        ev_pri = np.asarray(ev_pri)
        state = learner.add_at(state, staged, pris, start)
        live = int((ev_pri > 0).sum())
        store.put(ev_host, ev_pri, live)
        return state

    # fill BOTH cold stores to capacity first so every timed dispatch
    # runs with the admission door live — in the on arm that means
    # spill traffic on every put, the worst case for the ship path
    for store in (cold_off, cold_on):
        fills = 0
        while store.transitions < cold_cap \
                and fills < 4 * (cold_cap // block_tr + 1):
            state = swap_once(state, store)
            fills += 1
    jax.block_until_ready(state.replay.tree)

    steps, dispatches = args.steps_per_dispatch, args.dispatches
    off_rates, on_rates = [], []
    for _ in range(args.repeats):
        t0 = time.monotonic()
        for _ in range(dispatches):
            state = swap_once(state, cold_off)
            state, m = learner.train_many(state, steps)
        jax.block_until_ready(m["loss"])
        off_rates.append(steps * dispatches / (time.monotonic() - t0))
        t0 = time.monotonic()
        for _ in range(dispatches):
            state = swap_once(state, cold_on)
            state, m = learner.train_many(state, steps)
        jax.block_until_ready(m["loss"])
        on_rates.append(steps * dispatches / (time.monotonic() - t0))
    gsps_off = float(np.median(off_rates))
    gsps_on = float(np.median(on_rates))
    on_off = gsps_on / gsps_off if gsps_off else 0.0
    log(f"tiered-disk A/B: off {spread(off_rates)} vs on "
        f"{spread(on_rates)} grad-steps/s (on/off {on_off:.3f})")

    # retention soak: spill until the DISK holds the target multiple
    # of the cold tier's capacity (writeback is async, so poll the
    # store's own transition count, not the swap count)
    max_swaps = 8 * (target // block_tr + 1)
    swaps = 0
    t0 = time.monotonic()
    while disk.transitions < target and swaps < max_swaps:
        state = swap_once(state, cold_on)
        swaps += 1
        if swaps % 16 == 0:
            # let a deep backlog land; offer() itself never waits
            time.sleep(0.01)
    try:
        disk.drain(timeout=60.0)
    except TimeoutError:
        log("tiered-disk: writeback drain timed out — counting what "
            "landed")
    soak_s = time.monotonic() - t0
    dstats = disk.stats()
    retention = dstats["transitions"] / cold_cap if cold_cap else 0.0
    log(f"tiered-disk soak: {swaps} swaps -> {dstats['transitions']} "
        f"disk transitions in {dstats['segments']} segments across "
        f"{dstats['files']} files in {soak_s:.1f}s (retention "
        f"{retention:.2f}x cold, queue_full {dstats['queue_full']}, "
        f"io_errors {dstats['io_errors']})")

    # promote readback: heaviest segments off disk, CRC-checked
    rec_segments = min(dstats["segments"], 32)
    t0 = time.monotonic()
    promoted = disk.promote(rec_segments, floor=0.0)
    rec_s = time.monotonic() - t0
    rec_items = sum(s.live for s in promoted)
    promote_items_per_s = rec_items / rec_s if rec_s else 0.0
    log(f"tiered-disk promote: {len(promoted)} segments, {rec_items} "
        f"live transitions in {rec_s:.2f}s ({promote_items_per_s:,.0f} "
        f"items/s off disk)")
    disk.close()
    shutil.rmtree(disk_dir, ignore_errors=True)

    ok = (retention >= args.tiered_disk_mult
          and dstats["io_errors"] == 0
          and dstats["corrupt_segments"] == 0)
    result = {
        "metric": "tiered_disk_grad_steps_per_s_on",
        "value": float(f"{gsps_on:.4g}"),
        "unit": "steps/s",
        "ok": ok,
        "smoke": bool(args.smoke),
        "storage": storage,
        "capacity": capacity,
        "cold_capacity": cold_cap,
        "disk_capacity": disk_cap,
        "batch": batch,
        "block_transitions": block_tr,
        "codec": codec_status()[1],
        "grad_steps_per_s_off": spread(off_rates),
        "grad_steps_per_s_on": spread(on_rates),
        "on_off_frac": round(on_off, 4),
        "within_5pct": bool(on_off >= 0.95),
        "disk_transitions": dstats["transitions"],
        "disk_segments": dstats["segments"],
        "disk_files": dstats["files"],
        "disk_bytes": dstats["bytes"],
        "retention_vs_cold": round(retention, 3),
        "retention_target": float(args.tiered_disk_mult),
        "spilled": dstats["spilled"],
        "disk_dropped": dstats["dropped"],
        "queue_full": dstats["queue_full"],
        "io_errors": dstats["io_errors"],
        "corrupt_segments": dstats["corrupt_segments"],
        "compactions": dstats["compactions"],
        "promote_items_per_s": round(promote_items_per_s, 1),
        "door": {"stored": cold_on.stored, "dropped": cold_on.dropped,
                 "displaced": cold_on.displaced,
                 "spilled": cold_on.spilled},
    }
    line = json.dumps(result)
    gated = getattr(args, "perf_gate", False)
    rc = 0
    if gated:
        args._baseline = _load_tiered_disk_baseline(
            args.smoke, storage, capacity, cold_cap)
        rc = _gate_exit(result, args)
    if not ok:
        log(f"tiered-disk: criteria NOT met (retention "
            f"{retention:.2f}x vs >= {args.tiered_disk_mult}x cold "
            f"capacity, io_errors {dstats['io_errors']}, corrupt "
            f"{dstats['corrupt_segments']})")
        rc = rc or 1
    if rc == 0 or not gated:
        if ok:
            path = _tiered_disk_artifact_path(args.smoke)
            try:
                with open(path, "w") as fh:
                    fh.write(line + "\n")
            except OSError as e:
                log(f"could not write tiered-disk artifact {path}: "
                    f"{e!r}")
    else:
        log("tiered-disk perf-gate: artifact of record NOT updated by "
            "this failing run")
    print(line, flush=True)
    raise SystemExit(rc)


def _serve_artifact_path(smoke: bool) -> str:
    """Artifact of record for the serving lane. Same smoke/full split
    as the main bench: a CI smoke run only ever gates against a smoke
    baseline."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(here, "SERVE_SMOKE.json" if smoke
                        else "SERVE_LATEST.json")


def _load_serve_baseline(smoke: bool, tenants: int, max_batch: int,
                         vector: int) -> tuple[str | None, dict | None]:
    """Newest COMPARABLE serving artifact: same smoke class, same
    tenant count, batch budget and request vector. Aggregate
    forwards/s scales with all three — a cross-shape gate would fire
    on a shape change, not a regression."""
    path = _serve_artifact_path(smoke)
    try:
        with open(path) as fh:
            doc = json.load(fh)
    except (OSError, json.JSONDecodeError):
        return None, None
    if not (isinstance(doc, dict) and "metric" in doc
            and "value" in doc):
        return None, None
    if (doc.get("tenants") != tenants
            or doc.get("max_batch") != max_batch
            or doc.get("vector") != vector):
        log(f"serve gate: {os.path.basename(path)} is "
            f"{doc.get('tenants')}t@{doc.get('max_batch')}"
            f"v{doc.get('vector')}, this run is "
            f"{tenants}t@{max_batch}v{vector} — not comparable, "
            f"skipped")
        return None, None
    return path, doc


def _serve_mlp_family(rng):
    """Apply family for the serving lane: a shared frozen torso (baked
    into the jit as closure constants — identical for every tenant)
    with a small per-tenant head. This is the tier's intended coalesce
    regime (see _make_gather_apply: "many small per-tenant heads over
    a shared torso", the atari57-rotation shape at bench scale) AND
    what makes the A/B honest on a CPU host: torso compute dominates,
    so the gather-indexed forward pays only the per-example HEAD
    gather, not a per-example copy of the whole net."""
    d_in, d_h, d_out, layers = 256, 512, 8, 6
    torso = [jnp.asarray(rng.standard_normal(
                 (d_in if i == 0 else d_h, d_h)).astype(np.float32)
             * 0.02) for i in range(layers)]

    def apply(params, x):
        h = x
        for w in torso:
            h = jnp.tanh(h @ w)
        return h @ params["head_w"] + params["head_b"]

    def make_params():
        return {
            "head_w": rng.standard_normal(
                (d_h, d_out)).astype(np.float32),
            "head_b": rng.standard_normal(d_out).astype(np.float32),
        }

    return apply, make_params, d_in


def _serve_closed_loop(query_fns, vector: int, d_in: int, *,
                       rounds: int = 0,
                       window_s: float = 0.0) -> float:
    """Closed-loop load: one client thread per entry in query_fns,
    each pushing vector requests back-to-back. With `rounds`, every
    client sends exactly that many requests (the warm-up pre-pass).
    With `window_s`, every client keeps sending until the wall-clock
    deadline — fixed-work loops under a mixed priority split develop
    a convoy tail (top-class clients finish first, the stragglers run
    unpipelined and drag the aggregate), so the TIMED arms always use
    the window form: concurrency stays at full fan-in for the whole
    measurement. Returns aggregate forwards/s (items, not
    requests)."""
    import threading

    x = np.ones((vector, d_in), np.float32)
    errors: list[Exception] = []
    counts = [0] * len(query_fns)

    def client(idx, q):
        try:
            if window_s > 0:
                while time.monotonic() < t_end:
                    q(x, vector)
                    counts[idx] += 1
            else:
                for _ in range(rounds):
                    q(x, vector)
                    counts[idx] += 1
        except Exception as e:  # noqa: BLE001 - re-raised below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(i, q),
                                daemon=True)
               for i, q in enumerate(query_fns)]
    t0 = time.monotonic()
    t_end = t0 + window_s
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.monotonic() - t0
    if errors:
        raise errors[0]
    return sum(counts) * vector / dt if dt else 0.0


def bench_serve_ab(args) -> None:
    """Multi-tenant serving A/B (ISSUE 13): aggregate inference
    forwards/s through the continuous-batching serving tier
    (MultiPolicyInferenceServer — per-tenant params, mixed priority
    classes, coalesced gather-indexed forwards) vs the single-tenant
    BatchedInferenceServer at identical model/batch/client shapes,
    both orders. Then an overload phase: 2x the measured capacity
    offered open-loop across the priority mix — the admission
    controller must shed ONLY from the lower classes while the top
    class's per-tenant p99 stays inside the INSTRUMENTS healthy range,
    and the shed accounting must close (offered == admitted +
    shed_by_class).

    Artifact: SERVE_LATEST.json (SERVE_SMOKE.json under --smoke);
    --perf-gate gates aggregate multi-tenant forwards/s against the
    newest comparable artifact with the anti-ratchet rule."""
    import threading

    from ape_x_dqn_tpu.obs.report import HEALTHY
    from ape_x_dqn_tpu.parallel.inference_server import (
        BatchedInferenceServer, MultiPolicyInferenceServer,
        ServeDeadlineExceeded, ServeShed)

    tenants = args.serve_tenants
    max_batch, deadline_ms = args.serve_max_batch, 2.0
    vector, window_s = args.serve_vector, args.serve_window_s
    rng = np.random.default_rng(11)
    apply, make_params, d_in = _serve_mlp_family(rng)
    all_params = [make_params() for _ in range(tenants)]
    example = np.zeros(d_in, np.float32)
    # priority mix: top quarter class 0, next quarter class 1, rest
    # class 2 — the "rotation flagships + everyone else" shape
    prio = [0 if i < max(tenants // 4, 1)
            else (1 if i < max(tenants // 2, 2) else 2)
            for i in range(tenants)]

    # warm every pow2 bucket a coalesced batch can land in (partial
    # batches hit intermediate buckets; a cold compile inside the
    # timed loop would swamp these second-scale arms)
    warm_sizes = tuple(sorted({vector} | {
        1 << i for i in range(max_batch.bit_length())
        if 1 << i <= max_batch}))

    def run_single() -> float:
        server = BatchedInferenceServer(apply, all_params[0],
                                        max_batch=max_batch,
                                        deadline_ms=deadline_ms)
        try:
            server.warmup(example, extra_sizes=warm_sizes)
            # untimed pre-pass: reach scheduling steady state first
            _serve_closed_loop([server.query_batch] * tenants,
                               vector, d_in, rounds=2)
            return _serve_closed_loop([server.query_batch] * tenants,
                                      vector, d_in,
                                      window_s=window_s)
        finally:
            server.stop()

    def build_tier(slo_items: int, request_deadline_ms: float = 0.0):
        tier = MultiPolicyInferenceServer(
            max_batch=max_batch, deadline_ms=deadline_ms,
            priority_classes=3, queue_slo_items=slo_items,
            request_deadline_ms=request_deadline_ms)
        clients = [tier.register_policy(f"tenant{i:02d}", apply,
                                        all_params[i], family="mlp",
                                        priority=prio[i])
                   for i in range(tenants)]
        # warm AFTER registering every same-family tenant: the
        # coalesced compile shape includes the tenant count
        for c in clients:
            c.warmup(example, extra_sizes=warm_sizes)
        return tier, clients

    def run_multi() -> float:
        tier, clients = build_tier(slo_items=1 << 16)  # no shedding
        try:
            _serve_closed_loop([c.query_batch for c in clients],
                               vector, d_in, rounds=2)
            rate = _serve_closed_loop([c.query_batch for c in clients],
                                      vector, d_in,
                                      window_s=window_s)
            s = tier.stats
            assert s["shed"] == 0, s  # phase A is below the SLO line
            return rate
        finally:
            tier.stop()

    # A/B both orders: shared-host noise is order-correlated, so a
    # one-order run can manufacture (or hide) a 10% gap
    arms: dict[str, list[float]] = {"single": [], "multi": []}
    orders = []
    pairs = [("single", "multi"), ("multi", "single")] * args.serve_repeats
    for names in pairs:
        for name in names:
            arms[name].append(run_single() if name == "single"
                              else run_multi())
        orders.append(arms["multi"][-1] / arms["single"][-1]
                      if arms["single"][-1] else 0.0)
        log(f"serve A/B ({'->'.join(names)}): single "
            f"{arms['single'][-1]:,.0f} vs multi "
            f"{arms['multi'][-1]:,.0f} forwards/s "
            f"(multi/single {orders[-1]:.3f})")
    single_fps = float(np.median(arms["single"]))
    multi_fps = float(np.median(arms["multi"]))
    multi_vs_single = multi_fps / single_fps if single_fps else 0.0
    within_10pct = bool(multi_vs_single >= 0.9)

    # overload phase: 2x the measured multi-tenant capacity offered
    # open-loop across the priority mix; the SLO line is a small
    # multiple of the batch budget so the controller actually works
    slo_items = 4 * max_batch
    tier, clients = build_tier(slo_items,
                               request_deadline_ms=args.serve_deadline_ms)
    # untimed pre-pass: the p99 claim is about the admission
    # controller under sustained overload, not the first-dispatch
    # pipeline fill (measured: the whole tail of a cold start lands
    # in the first ~20ms). The controller is already live here —
    # deadline expiry and shedding on pre-pass requests are expected
    # outcomes, not errors
    pre_x = np.ones((vector, d_in), np.float32)
    for ticket in [c.submit(pre_x, vector)
                   for _ in range(2) for c in clients]:
        try:
            ticket.wait(timeout=30.0)
        except (ServeShed, ServeDeadlineExceeded):
            pass
    offered_rate = 2.0 * multi_fps
    window_s = args.serve_overload_s
    period = tenants * vector / offered_rate if offered_rate else 0.01
    tickets: list[tuple[int, object]] = []
    x = np.ones((vector, d_in), np.float32)
    t0 = time.monotonic()
    next_t = t0
    while time.monotonic() - t0 < window_s:
        for i, c in enumerate(clients):
            tickets.append((prio[i], c.submit(x, vector)))
        next_t += period
        lag = next_t - time.monotonic()
        if lag > 0:
            time.sleep(lag)
    outcomes = {"served": 0, "shed": 0, "expired": 0}
    by_class_shed = [0, 0, 0]
    for cls, t in tickets:
        try:
            t.wait(timeout=30.0)
            outcomes["served"] += 1
        except ServeDeadlineExceeded:
            outcomes["expired"] += 1
            by_class_shed[cls] += 1
        except ServeShed:
            outcomes["shed"] += 1
            by_class_shed[cls] += 1
    stats = tier.stats
    top_ids = [c.policy_id for c in clients
               if c.priority == 0]
    top_p99 = max(float(tier.tenant_stats(pid).get("p99_ms", 0.0))
                  for pid in top_ids)
    tier.stop()
    p99_bound = HEALTHY["infer_latency_ms"][1]
    closure = bool(stats["offered"]
                   == stats["admitted"] + sum(stats["shed_by_class"]))
    shed_frac = ((outcomes["shed"] + outcomes["expired"])
                 / max(len(tickets), 1))
    log(f"serve overload: offered {len(tickets)} requests "
        f"(~2x capacity for {window_s:.1f}s), served "
        f"{outcomes['served']}, shed {outcomes['shed']}, expired "
        f"{outcomes['expired']} ({shed_frac:.1%} relief), "
        f"top-class p99 {top_p99:.1f}ms (healthy < {p99_bound}), "
        f"shed_by_class {stats['shed_by_class']}")

    ok = (within_10pct and closure
          and stats["shed_by_class"][0] == 0
          and by_class_shed[0] == 0
          and top_p99 < p99_bound)
    result = {
        "metric": "serve_forwards_per_s",
        "value": float(f"{multi_fps:.4g}"),
        "unit": "forwards/s",
        "ok": ok,
        "smoke": bool(args.smoke),
        "tenants": tenants,
        "max_batch": max_batch,
        "vector": vector,
        "priority_mix": prio,
        "single_forwards_per_s": spread(arms["single"]),
        "multi_forwards_per_s": spread(arms["multi"]),
        "multi_vs_single": round(multi_vs_single, 4),
        "within_10pct": within_10pct,
        "order_fracs": [round(o, 4) for o in orders],
        "overload": {
            "offered_requests": len(tickets),
            "served": outcomes["served"],
            "shed": outcomes["shed"],
            "expired": outcomes["expired"],
            "shed_frac": round(shed_frac, 4),
            "shed_by_class": stats["shed_by_class"],
            "accounting_closed": closure,
            "top_class_p99_ms": round(top_p99, 2),
            "p99_healthy_bound": p99_bound,
        },
    }
    line = json.dumps(result)
    gated = getattr(args, "perf_gate", False)
    rc = 0
    if gated:
        args._baseline = _load_serve_baseline(args.smoke, tenants,
                                              max_batch, vector)
        rc = _gate_exit(result, args)
    if not ok:
        log(f"serve: criteria NOT met (multi/single "
            f"{multi_vs_single:.3f} vs >= 0.9; top-class p99 "
            f"{top_p99:.1f}ms vs < {p99_bound}; class-0 shed "
            f"{stats['shed_by_class'][0]} vs 0; accounting closed: "
            f"{closure})")
        rc = rc or 1
    if rc == 0 or not gated:
        if ok:
            path = _serve_artifact_path(args.smoke)
            try:
                with open(path, "w") as fh:
                    fh.write(line + "\n")
            except OSError as e:
                log(f"could not write serve artifact {path}: {e!r}")
    else:
        log("serve perf-gate: artifact of record NOT updated by this "
            "failing run")
    print(line, flush=True)
    raise SystemExit(rc)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--capacity", type=int, default=1 << 20,
                   help="replay capacity in transitions — default is "
                   "the shipping pong preset's effective capacity "
                   "(1M rounded to 2^20; ~9.7KB HBM per transition as "
                   "packed frame-ring byte rows, ~9.63GiB total). "
                   "Earlier rounds benched at 2^18 because the "
                   "pre-byte-row layout OOMed at preset scale — "
                   "PERF.md 'HBM budget'")
    p.add_argument("--batch-size", type=int, default=512)
    p.add_argument("--prefill", type=int, default=1 << 15)
    p.add_argument("--steps-per-dispatch", type=int, default=50)
    p.add_argument("--dispatches", type=int, default=10)
    p.add_argument("--storage", choices=("frame_ring", "flat"),
                   default="frame_ring",
                   help="replay layout; frame_ring is the flagship "
                   "(replay/frame_ring.py)")
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="capture a JAX profiler trace of the timed "
                   "train_many dispatches into DIR")
    p.add_argument("--actor-frames", type=int, default=2000,
                   help="frames per actor for the env-frames/s bench "
                   "(0 disables it)")
    p.add_argument("--actor-count", type=int, default=2)
    p.add_argument("--envs-per-actor", type=int, default=16)
    p.add_argument("--repeats", type=int, default=3,
                   help="measurement repeats for median + spread")
    p.add_argument("--sample-chunk", type=int, default=4,
                   help="K-batch sampling relaxation "
                   "(LearnerConfig.sample_chunk): K grad-steps per "
                   "stratified sample + priority write-back. Default 4 "
                   "= the shipping flagship presets (PERF.md 'K-batch "
                   "sampling'); 1 = exact per-step semantics "
                   "(measures ~3-5% lower)")
    p.add_argument("--prefetch-ab", action="store_true",
                   help="run the double-buffered-sampler A/B "
                   "(LearnerConfig.sample_prefetch off vs on, both "
                   "orders, median-of-`--repeats` per arm) for the "
                   "flat DQN AND R2D2 sequence families, recorded "
                   "under secondary.prefetch_ab (PERF.md 'Prefetch "
                   "A/B'). Runs at the --ab-* shapes, INSTEAD of the "
                   "main flagship bench (the stdout metric is then "
                   "the flat off-arm median)")
    p.add_argument("--ingest-ab", action="store_true",
                   help="run the zero-copy ingest staging A/B (legacy "
                   "list-append + concatenate staging vs the pipelined "
                   "stager, both orders, median-of-`--repeats` per "
                   "arm): live_gap = grad-steps/s under a saturating "
                   "concurrent ingest stream / offline grad-steps/s, "
                   "recorded under secondary.ingest_ab (PERF.md "
                   "'Ingest pipeline'). Runs at the --ab-* shapes for "
                   "--storage, INSTEAD of the main flagship bench "
                   "(the stdout metric is then the old-arm offline "
                   "median)")
    p.add_argument("--wire-ab", action="store_true",
                   help="run the wire-codec A/B (raw vs delta-deflate "
                   "experience compression over a real loopback socket "
                   "pair, both orders, median-of-`--repeats` per arm, "
                   "plus a bandwidth-capped arm paced to "
                   "--wire-ab-cap-mb): bytes/transition + items/s, "
                   "recorded under secondary.wire_ab (PERF.md 'Wire "
                   "codec'). Runs INSTEAD of the main flagship bench")
    p.add_argument("--telemetry-ab", action="store_true",
                   help="run the fleet-telemetry overhead A/B "
                   "(obs/fleet.py plane fully on — batch stamping, "
                   "frame pump, learner-side aggregation — vs fully "
                   "off, over a real loopback socket pair, both "
                   "orders, median-of-`--repeats` per arm): items/s "
                   "overhead plus the side-channel's own frames/s and "
                   "bytes/s, recorded under secondary.telemetry_ab "
                   "(PERF.md 'Observability'). Runs INSTEAD of the "
                   "main flagship bench")
    p.add_argument("--wire-ab-cap-mb", type=float, default=10.5,
                   help="simulated link MB/s for the capped wire-ab "
                   "arm (default = the round-4 measured live ingest "
                   "rate)")
    p.add_argument("--shm-ab", action="store_true",
                   help="run the shared-memory transport A/B INSTEAD "
                   "of the main bench (comm/shm_transport.py, ISSUE "
                   "18): ingest items/s with the same-host shm "
                   "experience ring + doorbell plane vs plain TCP "
                   "loopback at the default delta-deflate codec, over "
                   "real server/transport pairs, both orders, "
                   "median-of-`--repeats` per arm, an uncapped arm "
                   "(one producer) plus a contended arm "
                   "(--shm-ab-producers concurrent producers); every "
                   "arm must close its slot/drop accounting (offered "
                   "== delivered + torn + dropped, zero torn "
                   "delivered) before its number counts. Writes "
                   "SHM_LATEST.json (SHM_SMOKE.json under --smoke; "
                   "PERF.md 'Shared-memory transport')")
    p.add_argument("--shm-ab-producers", type=int, default=3,
                   help="concurrent producer transports in the "
                   "shm-ab contended arm (the same-host actor-process "
                   "fan-in the shm plane exists for; >= 2)")
    p.add_argument("--shm-ab-bar", type=float, default=2.0,
                   help="adoption bar for the shm lane: shm must "
                   "reach this multiple of the TCP arm's contended "
                   "items/s in BOTH orders (2 = the ISSUE 18 "
                   "acceptance bar)")
    p.add_argument("--shm-ab-slots", type=int, default=8,
                   help="experience-ring slots per shm connection in "
                   "the shm lane (slot bytes are sized to one "
                   "raw-encoded message automatically)")
    p.add_argument("--params-ab", action="store_true",
                   help="run the param-plane codec A/B INSTEAD of the "
                   "main bench (comm/param_codec.py, ISSUE 19): wire "
                   "bytes per weight publish to --params-ab-subs real "
                   "push subscribers, delta-q8 vs raw, both orders, "
                   "median-of-`--repeats` per arm, plus a token-bucket "
                   "capped-link run, a quantized-policy greedy-parity "
                   "smoke and a slow-subscriber isolation arm (one "
                   "wedged never-reading peer; healthy-peer latency "
                   "must hold and its deposits must supersede). "
                   "Writes PARAMS_LATEST.json (PARAMS_SMOKE.json "
                   "under --smoke; PERF.md 'Param-plane codec')")
    p.add_argument("--params-ab-subs", type=int, default=3,
                   help="push subscribers per params-ab arm (the "
                   "actor-host fan-out each publish pays for; >= 2)")
    p.add_argument("--params-ab-bar", type=float, default=3.0,
                   help="adoption bar for the params lane: delta-q8 "
                   "must cut bytes/publish by this multiple vs raw in "
                   "BOTH orders (3 = the ISSUE 19 acceptance bar)")
    p.add_argument("--params-ab-cap-mb", type=float, default=8.0,
                   help="simulated link MB/s for the capped params-ab "
                   "run (DCN-scale weight-broadcast budget; the byte "
                   "saving converts to publish rate here)")
    p.add_argument("--chaos-ab", action="store_true",
                   help="run the chaos-lane A/B instead of the main "
                   "bench (same sender fleet through a ChaosProxy, "
                   "clean link vs garble + cut + learner restart "
                   "inside the timed window, median-of-`--repeats` "
                   "per arm): availability ratio, reconnect latency, "
                   "fault attribution counters")
    p.add_argument("--chaos-ab-seconds", type=float, default=4.0,
                   help="timed window per chaos-ab arm; the fault "
                   "schedule (garble phase, cut, restart outage) is "
                   "proportional to it")
    p.add_argument("--multichip", default=None,
                   metavar="[virtual:]dp=1,2,4",
                   help="run the dp-scaling sweep INSTEAD of the main "
                   "bench: one fresh child process per dp point, each "
                   "seeing the same device count, building the "
                   "dp-sharded frame-ring stack (dist dqn learner) and "
                   "timing lockstep ingest + fused train_many. "
                   "'dp=1,2,4' (or '1,2,4') runs on real devices and "
                   "fails if a child finds fewer than max(dp); "
                   "'virtual:dp=1,2,4' asks by name for "
                   "XLA_FLAGS=--xla_force_host_platform_device_count "
                   "virtual CPU devices. Writes MULTICHIP_<round>.json "
                   "+ an obs-format metrics JSONL for obs/report.py "
                   "(PERF.md 'Multi-chip scaling')")
    p.add_argument("--multichip-child", default=None,
                   metavar="DP/DEVICES", help=argparse.SUPPRESS)
    p.add_argument("--tiered-ab", action="store_true",
                   help="run the tiered-replay A/B INSTEAD of the main "
                   "bench (replay/cold_store.py, ROADMAP item 3): "
                   "grad-steps/s with every ingest block riding the "
                   "ring-full eviction swap (lowest-priority-mass "
                   "region -> delta+deflate host-RAM cold store, fresh "
                   "block in via the directed add_at) vs the plain "
                   "FIFO add path, plus a capacity soak (the cold "
                   "tier must hold --tiered-ring-mult x the ring's "
                   "transitions at < 1/8 of its bytes/transition) and "
                   "recall decompress throughput. Writes "
                   "TIERED_LATEST.json (TIERED_SMOKE.json under "
                   "--smoke; PERF.md 'Tiered replay')")
    p.add_argument("--tiered-cold-capacity", type=int, default=0,
                   help="cold-tier capacity in transitions for the "
                   "tiered lane (0 = 16x --capacity, enough headroom "
                   "for the 8x soak target before the admission door "
                   "engages)")
    p.add_argument("--tiered-block", type=int, default=1024,
                   help="transitions per eviction swap block in the "
                   "tiered lane (rounded down to whole frame segments "
                   "under --storage frame_ring; capped at capacity/4)")
    p.add_argument("--tiered-ring-mult", type=float, default=8.0,
                   help="capacity-soak target: the cold tier must end "
                   "up holding this multiple of the ring's transitions "
                   "(8 = the tiering acceptance bar)")
    p.add_argument("--tiered-disk", action="store_true",
                   help="with --tiered-ab: run the DISK arm instead "
                   "(replay/disk_store.py, PR 16): the same eviction-"
                   "swap loop with the cold store's admission-door "
                   "losers spilling to the async disk writeback vs "
                   "spill off, plus a retention soak (disk must hold "
                   "--tiered-disk-mult x the cold tier's capacity) "
                   "and promote() readback throughput. Writes "
                   "TIERED_DISK_LATEST.json (TIERED_DISK_SMOKE.json "
                   "under --smoke; PERF.md 'Disk tier')")
    p.add_argument("--tiered-disk-mult", type=float, default=8.0,
                   help="disk-arm retention target: the disk rung "
                   "must end up holding this multiple of the cold "
                   "tier's transitions (8 = the acceptance bar)")
    p.add_argument("--tiered-disk-queue", type=int, default=16,
                   help="writeback queue depth for the disk arm "
                   "(full-queue offers are counted, never waited on)")
    p.add_argument("--serve-ab", action="store_true",
                   help="run the multi-tenant serving A/B INSTEAD of "
                   "the main bench (parallel/inference_server.py "
                   "serving tier): aggregate inference forwards/s "
                   "through the continuous-batching "
                   "MultiPolicyInferenceServer (per-tenant params, "
                   "mixed priority classes, coalesced gather-indexed "
                   "forward) vs the single-tenant "
                   "BatchedInferenceServer at identical shapes, both "
                   "orders, plus a 2x-capacity overload phase "
                   "(admission controller must shed only lower "
                   "classes while the top class's p99 stays inside "
                   "the INSTRUMENTS healthy range). Writes "
                   "SERVE_LATEST.json (SERVE_SMOKE.json under "
                   "--smoke; PERF.md 'Serving tier')")
    p.add_argument("--serve-tenants", type=int, default=8,
                   help="tenant count for the serving lane (>= 8 is "
                   "the acceptance shape; split 1/4 class 0, 1/4 "
                   "class 1, 1/2 class 2)")
    p.add_argument("--serve-max-batch", type=int, default=64,
                   help="serving-tier batch budget for the serve lane")
    p.add_argument("--serve-vector", type=int, default=16,
                   help="items per request in the serve lane (the "
                   "vector-actor request shape)")
    p.add_argument("--serve-repeats", type=int, default=3,
                   help="A/B order-pair repeats in the serve lane "
                   "(each repeat runs both orders; medians pool over "
                   "all runs per arm)")
    p.add_argument("--serve-window-s", type=float, default=2.0,
                   help="fixed wall-clock measurement window "
                   "(seconds) per A/B arm in the serve lane — "
                   "clients send back-to-back until the deadline so "
                   "concurrency never collapses into a "
                   "fixed-work convoy tail")
    p.add_argument("--serve-overload-s", type=float, default=4.0,
                   help="open-loop overload window (seconds) for the "
                   "serve lane's shedding phase")
    p.add_argument("--serve-deadline-ms", type=float, default=250.0,
                   help="per-request admission deadline (ms) during "
                   "the serve lane's overload phase (0 disables "
                   "deadline expiry; shedding then rides the SLO "
                   "line only)")
    p.add_argument("--learn-health", action="store_true",
                   help="run the learning-health smoke lane INSTEAD of "
                   "the main bench: short real training runs (one per "
                   "env family) through the single-process driver with "
                   "the obs plane on, writing LEARN_HEALTH_SMOKE.jsonl "
                   "+ a SUITE_LEARN-style LEARN_HEALTH_SMOKE.json with "
                   "per-tenant learn_* gauges and health verdicts. "
                   "Gate the JSONL with `python -m "
                   "ape_x_dqn_tpu.obs.report ... --check`")
    p.add_argument("--lh-frames", type=int, default=1400,
                   help="env frames per game for the --learn-health "
                   "lane")
    p.add_argument("--blackbox-ab", action="store_true",
                   help="run the flight-recorder overhead A/B INSTEAD "
                   "of the main bench (obs/blackbox.py, ISSUE 17): "
                   "the same short real training run with the "
                   "FlightRecorder on vs ObsConfig.blackbox=False, "
                   "both orders x --repeats, plus a dump round-trip "
                   "check and a no-stray-dump check on the healthy "
                   "runs. Writes BLACKBOX_LATEST.json "
                   "(BLACKBOX_SMOKE.json under --smoke; PERF.md "
                   "'Flight recorder'); the full lane gates the "
                   "on/off grad-steps/s ratio at >= 0.95")
    p.add_argument("--bb-frames", type=int, default=1400,
                   help="env frames per arm for the --blackbox-ab "
                   "lane")
    p.add_argument("--ab-batch-size", type=int, default=64,
                   help="batch size for the prefetch A/B arms (small "
                   "enough to iterate on a CPU host; raise on a real "
                   "chip)")
    p.add_argument("--ab-capacity", type=int, default=1 << 14)
    p.add_argument("--ab-steps-per-dispatch", type=int, default=32)
    p.add_argument("--ab-dispatches", type=int, default=4)
    p.add_argument("--smoke", action="store_true",
                   help="CI-sized shapes (tiny capacity/batch, 1 "
                   "repeat, no actor bench): seconds, not minutes, on "
                   "a CPU host. Writes BENCH_SMOKE.json so smoke runs "
                   "are only ever gated against smoke runs")
    p.add_argument("--perf-gate", action="store_true",
                   help="after the bench, compare this run's headline "
                   "value against the newest comparable BENCH_*.json "
                   "artifact and exit nonzero when it falls below "
                   "--gate-frac of the baseline (the CI perf gate; "
                   "no baseline = pass-and-seed)")
    p.add_argument("--gate-frac", type=float, default=0.7,
                   help="perf-gate threshold: fail when value < "
                   "gate_frac * baseline (default 0.7 — generous "
                   "enough for shared-host noise, tight enough to "
                   "catch a real dispatch-path regression)")
    p.add_argument("--throttle-ms", type=float, default=0.0,
                   help="inject a host sleep (ms) per timed learner "
                   "dispatch — the perf-gate's test hook for an "
                   "artificially slowed run")
    args = p.parse_args()
    ensure_compile_cache()
    if args.smoke:
        args.capacity = min(args.capacity, 1 << 12)
        args.batch_size = min(args.batch_size, 32)
        args.prefill = min(args.prefill, 1 << 10)
        args.steps_per_dispatch = min(args.steps_per_dispatch, 8)
        args.dispatches = min(args.dispatches, 2)
        args.repeats = 1
        args.actor_frames = 0
        # the A/B lanes (live soak rides the default lane) share these
        args.ab_capacity = min(args.ab_capacity, 1 << 12)
        args.ab_batch_size = min(args.ab_batch_size, 16)
        args.ab_steps_per_dispatch = min(args.ab_steps_per_dispatch, 4)
        args.ab_dispatches = min(args.ab_dispatches, 2)
        args.chaos_ab_seconds = min(args.chaos_ab_seconds, 2.0)
        args.lh_frames = min(args.lh_frames, 800)
        args.bb_frames = min(args.bb_frames, 600)
        args.tiered_block = min(args.tiered_block, 512)
        # serve_vector stays at the full-lane value: in-flight items
        # (tenants x vector = 2 full batches) give both arms the same
        # pipelining; halving it would change what the A/B measures
        args.serve_window_s = min(args.serve_window_s, 0.6)
        args.serve_overload_s = min(args.serve_overload_s, 1.5)
    # the baseline must be read BEFORE _emit overwrites the artifact
    args._baseline = (_load_baseline(args.smoke) if args.perf_gate
                      else (None, None))

    if args.multichip_child is not None:
        # one dp point of the sweep, running in the provisioned child
        # interpreter (see bench_multichip)
        bench_multichip_child(args)
        return
    if args.multichip:
        bench_multichip(args)
        return
    if args.learn_health:
        bench_learn_health(args)
        return
    if args.blackbox_ab:
        bench_blackbox_ab(args)
        return
    if args.tiered_ab:
        if args.tiered_disk:
            bench_tiered_disk(args)
        else:
            bench_tiered_ab(args)
        return
    if args.serve_ab:
        bench_serve_ab(args)
        return
    if args.shm_ab:
        bench_shm_ab(args)
        return
    if args.params_ab:
        bench_params_ab(args)
        return
    log(f"devices: {jax.devices()}")
    if args.prefetch_ab:
        ab = bench_prefetch_ab(args)
        gsps = ab["flat"]["off_first"]["off"]["median"]
        _emit({
            "metric": "learner_grad_steps_per_s",
            "value": round(gsps, 2),
            "unit": "steps/s",
            "vs_baseline": round(gsps / 19.0, 2),
            "secondary": {"prefetch_ab": ab},
        }, args)
        return
    if args.ingest_ab:
        ab = bench_ingest_ab(args)
        gsps = ab["old_first"]["old"]["offline"]["median"]
        _emit({
            "metric": "learner_grad_steps_per_s",
            "value": round(gsps, 2),
            "unit": "steps/s",
            "vs_baseline": round(gsps / 19.0, 2),
            "secondary": {"ingest_ab": ab,
                          "live_gap": ab["live_gap_new"]},
        }, args)
        return
    if args.telemetry_ab:
        ab = bench_telemetry_ab(args)
        worst = max(ab["overhead_pct"])
        _emit({
            "metric": "telemetry_overhead_pct",
            "value": worst,
            "unit": "%",
            "vs_baseline": round(
                ab["on_first"]["on_items_per_s"]["median"]
                / ab["on_first"]["off_items_per_s"]["median"], 3),
            "secondary": {"telemetry_ab": ab},
        }, args)
        return
    if args.wire_ab:
        ab = bench_wire_ab(args)
        _emit({
            "metric": "wire_bytes_per_transition",
            "value": ab["raw_first"]["delta-deflate"][
                "bytes_per_transition"],
            "unit": "bytes",
            "vs_baseline": ab["raw_first"]["delta-deflate"]["ratio"],
            "secondary": {"wire_ab": ab},
        }, args)
        return
    if args.chaos_ab:
        ab = bench_chaos_ab(args)
        result = {
            "metric": "chaos_availability_remediated",
            "value": ab["availability_remediated"],
            # vs_baseline = the remediation-off arm under the SAME
            # drill: the delta the engine is worth
            "vs_baseline": ab["availability"],
            "unit": "ratio",
            "window_s": ab["window_s"],
            "clients": ab["clients"],
            "postmortem": ab["remediated"].get("postmortem"),
            "secondary": {"chaos_ab": ab},
        }
        line = json.dumps(result)
        gated = getattr(args, "perf_gate", False)
        rc = 0
        # forensics gate (ISSUE 17), smoke and full alike — the drill
        # is deterministic about its faults, so the bundle must exist
        # and its root-cause line must name an injected component
        pmres = result["postmortem"] or {}
        if not (pmres.get("dumps", 0) > 0
                and os.path.exists(str(pmres.get("bundle", "")))
                and pmres.get("attributes_fault")):
            log(f"chaos gate FAIL: postmortem bundle missing or its "
                f"root cause does not attribute the injected fault — "
                f"{pmres}")
            rc = 1
        if gated:
            args._baseline = _load_chaos_baseline(
                args.smoke, ab["window_s"], ab["clients"])
            rc = _gate_exit(result, args)
        # the remediated arm must hold the pre-remediation
        # availability floor on the full lane (smoke windows are too
        # short for the ratio to be stable — the smoke lane gates via
        # the anti-ratchet artifact alone)
        if (not args.smoke
                and ab["availability_remediated"] < _CHAOS_AVAIL_FLOOR):
            log(f"chaos gate FAIL: remediated availability "
                f"{ab['availability_remediated']} below the recorded "
                f"pre-remediation floor {_CHAOS_AVAIL_FLOOR}")
            rc = rc or 1
        if rc == 0:
            path = _chaos_artifact_path(args.smoke)
            try:
                with open(path, "w") as fh:
                    fh.write(line + "\n")
            except OSError as e:
                log(f"could not write chaos artifact {path}: {e!r}")
        else:
            log("chaos perf-gate: artifact of record NOT updated by "
                "this failing run")
        print(line, flush=True)
        raise SystemExit(rc)
    h2d_rates = bench_h2d(repeats=args.repeats)
    log(f"h2d link: {spread(h2d_rates)} MB/s (pure device_put, 64MB "
        f"buffer) — read ingest items/s against this")
    net, learner, state, spec = build_learner(args.capacity, args.batch_size,
                                              args.storage,
                                              args.sample_chunk)
    state, ingest_rates = prefill(learner, state, spec, args.prefill,
                                  args.storage, repeats=args.repeats)

    rates, state = bench_learner(learner, state, args.steps_per_dispatch,
                                 args.dispatches, repeats=args.repeats,
                                 trace_dir=args.profile,
                                 throttle_ms=args.throttle_ms)
    gsps = float(np.median(rates))
    log(f"learner: {spread(rates)} grad-steps/s @ batch "
        f"{args.batch_size} = {gsps * args.batch_size:,.0f} samples/s "
        f"(capacity {args.capacity}, sample_chunk {args.sample_chunk})")
    secondary = {
        "learner_grad_steps_per_s": spread(rates),
        "ingest_items_per_s": spread(ingest_rates),
        "h2d_mb_per_s": spread(h2d_rates),
        "sample_chunk": args.sample_chunk,
        "wire_codec": wire_codec_summary(),
        "telemetry": telemetry_summary(args),
    }
    # learning-health snapshot (obs/learning.py): the in-graph diag
    # pytree from one extra already-compiled dispatch, so every BENCH
    # artifact records what the training math looked like at capture
    # time next to how fast it ran
    state, m = learner.train_many(state, args.steps_per_dispatch)
    jax.block_until_ready(m["loss"])
    if "diag" in m:
        secondary["learn_health"] = {
            k: float(f"{float(v):.4g}") for k, v in m["diag"].items()}
    flops = train_step_flops_analytic(args.batch_size)
    secondary["flops_per_step"] = round(flops)
    # the roof comes from the one peaks table, by device_kind; a device
    # the table does not know (the CPU backend) gets no achieved_tflops
    # and no mfu — a CPU rate is not written under a device metric
    peaks = device_peaks()
    if peaks is not None:
        achieved_tflops = gsps * flops / 1e12
        mfu = achieved_tflops * 1e12 / peaks[0]
        log(f"mfu: {flops / 1e9:.2f} GFLOP/step (analytic, 5-forward "
            f"double-DQN accounting) x {gsps:.0f} steps/s = "
            f"{achieved_tflops:.1f} TFLOP/s = {100 * mfu:.1f}% of "
            f"{peaks[0] / 1e12:.0f} peak ({jax.devices()[0].device_kind})")
        secondary["achieved_tflops"] = round(achieved_tflops, 2)
        secondary["mfu"] = round(mfu, 4)
    xla_flops = train_step_flops_xla(learner, state,
                                     args.steps_per_dispatch)
    if xla_flops is not None:
        secondary["flops_per_step_xla"] = round(xla_flops)
    sb, state = bench_stage_breakdown(learner, state, args.sample_chunk,
                                      repeats=args.repeats)
    secondary["stage_breakdown"] = sb
    state, add_rates = bench_add_device(learner, state, spec, args.storage)
    secondary["device_add_transitions_per_s"] = spread(add_rates)
    inf_rates = bench_inference(net, spec, repeats=args.repeats)
    log(f"inference: {spread(inf_rates)} forwards/s @ bucket 64")
    secondary["inference_forwards_per_s"] = spread(inf_rates)
    soak = bench_live_soak(args, zero_copy=True)
    secondary["live_gap"] = soak["live_gap"]
    secondary["live_soak"] = soak
    if args.actor_frames > 0:
        ab = bench_actor_pipeline(args.actor_count, args.envs_per_actor,
                                  args.actor_frames)
        log(f"actors: {ab['env_frames_per_s']:,.0f} env-frames/s "
            f"({ab['actors']} vector actors x {ab['envs_per_actor']} "
            f"envs, server avg_batch {ab['server_avg_batch']:.1f}) "
            f"[{os.cpu_count()} host cores]")
        secondary["actor_env_frames_per_s"] = round(
            ab["env_frames_per_s"], 1)
        secondary["actor_server_avg_batch"] = round(
            ab["server_avg_batch"], 2)

    try:
        from tools.apexlint import run as apexlint_run
        lint = apexlint_run(
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "ape_x_dqn_tpu"))
        # per_checker rows carry {findings, waivers, ms} — v3's
        # lifecycle/closure checkers and their timings ride along;
        # `closures` counts the statically-verified conservation laws
        secondary["apexlint"] = {"findings": len(lint["findings"]),
                                 "waivers": lint["waivers"],
                                 "per_checker": lint["per_checker"],
                                 "closures": len(lint["closures"])}
    except Exception as e:  # lint must never sink a bench run
        secondary["apexlint"] = {"error": repr(e)}

    baseline = 19.0  # Horgan et al. 2018: 1-GPU learner, batch 512
    _emit({
        "metric": "learner_grad_steps_per_s",
        "value": round(gsps, 2),
        "unit": "steps/s",
        "vs_baseline": round(gsps / baseline, 2),
        "secondary": secondary,
    }, args)


if __name__ == "__main__":
    main()
