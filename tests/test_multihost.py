"""Multi-host learner (parallel/multihost.py + runtime/multihost_driver
.py): two REAL OS processes form a global 8-device mesh over the JAX
distributed runtime (Gloo as the DCN stand-in on CPU) and train in SPMD
lockstep — the NCCL/MPI process-group equivalent (SURVEY.md §5
"distributed communication backend")."""

import functools
import json
import os
import socket
import subprocess
import sys
import textwrap

import pytest


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


_PROBE = textwrap.dedent("""\
    import sys
    import jax
    jax.distributed.initialize(coordinator_address=sys.argv[1],
                               num_processes=2,
                               process_id=int(sys.argv[2]))
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("probe")
""")


@functools.cache
def _two_process_supported() -> bool:
    """Probe whether this jax build can actually form a two-process
    Gloo group on the CPU backend (some wheels ship without the
    distributed CPU collectives; the real tests would then fail on
    environment grounds, not code grounds). One cached probe per
    pytest process: two tiny subprocesses initialize + barrier."""
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    procs = [subprocess.Popen(
        [sys.executable, "-c", _PROBE, f"127.0.0.1:{port}", str(pid)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        for pid in range(2)]
    try:
        return all(p.wait(timeout=120) == 0 for p in procs)
    except subprocess.TimeoutExpired:
        return False
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()


def _require_two_process():
    if not _two_process_supported():
        pytest.skip("two-process jax.distributed group unsupported on "
                    "this host's CPU backend (probe failed)")


_SETS = [
    "parallel.dp=8", "parallel.tp=1",
    "replay.kind=prioritized", "replay.capacity=4096",
    "replay.min_fill=64",
    "learner.batch_size=32", "learner.n_step=3",
    "learner.target_sync_every=100", "learner.publish_every=10",
    "learner.train_chunk=2",
    # envs_per_actor=2 routes the multihost local-actor path through
    # the vectorized actor (one query_batch per vector step)
    "actors.num_actors=1", "actors.base_eps=0.6", "actors.ingest_batch=8",
    "actors.envs_per_actor=2",
    "inference.max_batch=8", "inference.deadline_ms=1.0",
    "eval_every_steps=0", "eval_episodes=0",
]


def _launch(port, pid, extra, config="cartpole_smoke", sets=_SETS):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # 4 local devices per process -> dp=8 rows across two processes
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    return subprocess.Popen(
        [sys.executable, "-m", "ape_x_dqn_tpu.runtime.train",
         "--config", config,
         "--coordinator", f"127.0.0.1:{port}",
         "--num-processes", "2", "--process-id", str(pid)]
        + [a for s in sets for a in ("--set", s)]
        + extra,  # after sets: later --set wins
        env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def test_frame_budget_terminates_when_total_unreachable():
    """Per-actor budget truncation (1001 frames / 2 procs / 3 actors ->
    at most 996 produced) must not hang the frame-budget round loop:
    the all-hosts-idle check breaks it (regression: frames_global could
    never reach `total` and every process spun forever)."""
    _require_two_process()
    port = _free_port()
    procs = [_launch(port, pid,
                     ["--total-env-frames", "1001",
                      "--set", "actors.num_actors=3"])
             for pid in range(2)]
    outs = []
    for p in procs:
        stdout, stderr = p.communicate(timeout=300)
        assert p.returncode == 0, stderr[-3000:]
        outs.append(json.loads(stdout.strip().splitlines()[-1]))
    # per-actor truncation: 1001 // 2 procs // 3 actors = 166 each
    assert outs[0]["frames"] == outs[1]["frames"] <= 996
    assert outs[0]["frames"] > 0


def test_stall_watchdog_fires_and_aborts():
    """StallWatchdog (round-2 verdict weak #8): silence past the
    timeout emits a diagnostic naming the process; two consecutive
    silent windows invoke the fatal action; stamps reset strikes."""
    import time as _time

    from ape_x_dqn_tpu.runtime.multihost_driver import StallWatchdog

    events, codes = [], []
    wd = StallWatchdog(1.2, describe=lambda: "state-snapshot",
                       fatal=codes.append, emit=events.append)
    wd.start()
    try:
        # keep stamping well inside the window: must never fire (wide
        # margins — this box runs tests under heavy contention)
        for _ in range(4):
            _time.sleep(0.2)
            wd.stamp()
        assert events == [] and codes == []
        # go silent: strike 1 (diagnostic), then strike 2 (fatal)
        deadline = _time.monotonic() + 5
        while len(codes) == 0 and _time.monotonic() < deadline:
            _time.sleep(0.05)
        assert len(events) >= 2, events
        assert "state-snapshot" in events[0]
        assert "no round progress" in events[0]
        assert codes == [70], codes
    finally:
        wd.stop()


def test_stall_watchdog_disabled_at_zero():
    from ape_x_dqn_tpu.runtime.multihost_driver import StallWatchdog

    wd = StallWatchdog(0.0, describe=lambda: "",
                       fatal=lambda c: None, emit=lambda m: None)
    wd.start()  # must not start a thread
    assert not wd._thread.is_alive()
    wd.stop()


def test_stall_watchdog_stop_joins_thread():
    """Regression (apexlint v3 thread-lifecycle sweep): stop() must
    JOIN the watch thread, not just set the event — a watcher still
    running after stop() returns can fire a spurious diagnostic (or
    the fatal) into interpreter teardown."""
    from ape_x_dqn_tpu.runtime.multihost_driver import StallWatchdog

    wd = StallWatchdog(30.0, describe=lambda: "",
                       fatal=lambda c: None, emit=lambda m: None)
    wd.start()
    assert wd._thread.is_alive()
    wd.stop()
    assert not wd._thread.is_alive()


def test_multihost_steps_per_frame_cap_binds():
    """learner.steps_per_frame_cap must pace the lockstep learner to
    the GLOBAL frame count (and the fleet must still terminate when the
    cap binds forever after actors finish)."""
    _require_two_process()
    cap = 0.05
    port = _free_port()
    procs = [_launch(port, pid,
                     ["--total-env-frames", "800",
                      "--set", f"learner.steps_per_frame_cap={cap}"])
             for pid in range(2)]
    outs = []
    for p in procs:
        stdout, stderr = p.communicate(timeout=300)
        assert p.returncode == 0, stderr[-3000:]
        outs.append(json.loads(stdout.strip().splitlines()[-1]))
    assert outs[0]["grad_steps"] == outs[1]["grad_steps"]
    assert outs[0]["grad_steps"] > 0, outs
    # pacing rechecks before each <= train_chunk dispatch
    assert outs[0]["grad_steps"] <= cap * outs[0]["frames"] + 2, outs


def test_two_process_lockstep_training(tmp_path):
    _require_two_process()
    port = _free_port()
    procs = [_launch(port, pid,
                     ["--total-env-frames", "1600",
                      "--max-grad-steps", "20",
                      "--metrics-file", str(tmp_path / f"m{pid}.jsonl"),
                      # eval on process 0 (host-local, collective-free)
                      "--set", "eval_every_steps=5",
                      "--set", "eval_episodes=1"])
             for pid in range(2)]
    outs = []
    for p in procs:
        stdout, stderr = p.communicate(timeout=540)
        assert p.returncode == 0, stderr[-3000:]
        outs.append(json.loads(stdout.strip().splitlines()[-1]))
    for out in outs:
        assert out["grad_steps"] >= 20, out
        assert out["actor_errors"] == [], out
        assert out["frames"] > 0
        assert out["replay_filled"] >= 64
    # lockstep invariants: global quantities agree across processes,
    # and the final loss (computed from the same global batch) matches
    assert outs[0]["grad_steps"] == outs[1]["grad_steps"]
    assert outs[0]["frames"] == outs[1]["frames"]
    assert outs[0]["loss"] == pytest.approx(outs[1]["loss"], rel=1e-5)
    # both hosts actually contributed experience
    assert outs[0]["frames_local"] > 0 and outs[1]["frames_local"] > 0
    # eval ran on process 0 only, without perturbing the lockstep (the
    # grad_steps/frames/loss agreement above IS the non-perturbation
    # check), and its record carries a real return
    assert outs[0]["eval_error"] is None, outs[0]
    assert outs[0]["eval"] is not None and \
        outs[0]["eval"]["episodes"] >= 1, outs[0]
    assert outs[1]["eval"] is None, outs[1]
    # per-round metrics stream to --metrics-file (publish cadence)
    for pid in range(2):
        lines = (tmp_path / f"m{pid}.jsonl").read_text().splitlines()
        recs = [json.loads(ln) for ln in lines]
        assert any("loss" in r for r in recs), recs


_R2D2_SETS = [
    "parallel.dp=8", "parallel.tp=1",
    "env.id=CartPolePO", "env.kind=cartpole_po",
    "network.lstm_size=32", "network.torso_dense=64",
    "network.compute_dtype=float32",
    "replay.capacity=512", "replay.seq_length=16", "replay.seq_overlap=8",
    "replay.burn_in=4", "replay.min_fill=16", "replay.storage=flat",
    "learner.batch_size=16", "learner.n_step=3", "learner.lr=1e-3",
    "learner.target_sync_every=100", "learner.publish_every=10",
    "learner.train_chunk=2",
    # two envs a thread: the queries carry a [2] axis
    "actors.num_actors=1", "actors.base_eps=0.4", "actors.ingest_batch=64",
    "actors.envs_per_actor=2",
    "inference.max_batch=8", "inference.deadline_ms=1.0",
    "eval_every_steps=0", "eval_episodes=0",
]


def test_two_process_lockstep_r2d2():
    """R2D2 over the lockstep round loop: two OS processes, sequence
    replay shards + the LSTM sequence loss on one global 8-device mesh,
    recurrent actors querying stateful {obs,c,h} inference."""
    _require_two_process()
    port = _free_port()
    procs = [_launch(port, pid,
                     ["--total-env-frames", "2400",
                      "--max-grad-steps", "10"],
                     config="r2d2", sets=_R2D2_SETS)
             for pid in range(2)]
    outs = []
    for p in procs:
        stdout, stderr = p.communicate(timeout=540)
        assert p.returncode == 0, stderr[-3000:]
        outs.append(json.loads(stdout.strip().splitlines()[-1]))
    for out in outs:
        assert out["grad_steps"] >= 10, out
        assert out["actor_errors"] == [], out
        assert out["frames"] > 0
    # lockstep invariants hold for the sequence learner too
    assert outs[0]["grad_steps"] == outs[1]["grad_steps"]
    assert outs[0]["frames"] == outs[1]["frames"]
    assert outs[0]["loss"] == pytest.approx(outs[1]["loss"], rel=1e-5)
    assert outs[0]["frames_local"] > 0 and outs[1]["frames_local"] > 0


def test_multihost_checkpoint_resume(tmp_path):
    """Checkpoint/resume over the lockstep loop: run 1 trains 20 steps
    into a shared checkpoint dir (collective gather, process-0 write);
    run 2 restores on construction (min-agreement on the step) and
    continues the grad-step counter to a higher target."""
    _require_two_process()
    ckpt = str(tmp_path / "ckpt")
    extra = ["--total-env-frames", "100000", "--checkpoint-dir", ckpt]
    port = _free_port()
    procs = [_launch(port, pid, extra + ["--max-grad-steps", "20"])
             for pid in range(2)]
    outs = []
    for p in procs:
        stdout, stderr = p.communicate(timeout=420)
        assert p.returncode == 0, stderr[-3000:]
        outs.append(json.loads(stdout.strip().splitlines()[-1]))
    assert outs[0]["grad_steps"] == outs[1]["grad_steps"] == 20
    assert outs[0]["restored_step"] is None  # run 1 started fresh

    port = _free_port()
    procs = [_launch(port, pid, extra + ["--max-grad-steps", "30"])
             for pid in range(2)]
    outs = []
    for p in procs:
        stdout, stderr = p.communicate(timeout=420)
        assert p.returncode == 0, stderr[-3000:]
        outs.append(json.loads(stdout.strip().splitlines()[-1]))
    # resumed at 20 (marker proves restore actually fired, not a
    # silent fresh 0->30 run), trained on to 30, in lockstep
    assert outs[0]["restored_step"] == outs[1]["restored_step"] == 20
    assert outs[0]["grad_steps"] == outs[1]["grad_steps"] == 30
    assert outs[0]["loss"] == pytest.approx(outs[1]["loss"], rel=1e-5)
