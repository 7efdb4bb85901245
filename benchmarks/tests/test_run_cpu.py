"""The command refuses the CPU, and both traffic kinds run end to end
at a tiny size through `runner.run_cell` (the function behind the
command, minus the device gate)."""

import dataclasses
import json
import os
import subprocess
import sys
import time

from benchmarks.harness import cells, runner

ROOT = cells.ROOT
TINY = ("learner.batch_size=8", "replay.min_fill=512")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_run_py_on_the_cpu_exits_nonzero_with_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "pong_offline", "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def _tiny_run(name: str, overrides: tuple, traffic: dict | None = None,
              seconds: float = 1.0) -> dict:
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    cell = cells.resolve(name)
    if traffic:
        cell = dataclasses.replace(cell,
                                   traffic={**cell.traffic, **traffic})
    return runner.run_cell(cell, seed=3, seconds=seconds, trace=False,
                           t_process_start=time.monotonic(),
                           devices=jax.devices()[:cell.chips],
                           cfg_overrides=TINY + overrides)


def _assert_result_line(result: dict, cell_name: str) -> None:
    assert set(result) == RESULT_KEYS
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    cell = cells.resolve(cell_name)
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in cell.end_to_end:
        got = result["metrics"][m["name"]]
        assert set(got) == {"value", "unit"} and got["unit"] == m["unit"]
    json.dumps(result)   # every value is plain JSON


def test_offline_kind_tiny():
    result = _tiny_run("pong_offline", ("replay.capacity=4096",))
    _assert_result_line(result, "pong_offline")
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0


def test_fleet_kind_tiny_ledger_closes(monkeypatch):
    """offered = added + dropped closes exactly on a tiny ApexDriver.
    `correct` as a whole is not asserted here: on the CPU backend
    `jax.device_put` aliases small host arrays, so the stager's
    compaction can rewrite a staged row before `add` reads it and the
    ring-content check (rightly) sees it; a TPU copies. And at batch
    8 the 95% rule of the TD comparison is a maximum, which one argmax
    flip breaks; test_reference.py covers that comparison."""
    facts = {}
    real = cells.traffic_kind

    def spying(cell):
        kind = real(cell)

        def run(rt):
            facts.update(kind.run(rt))
            return facts
        return type("SpiedKind", (), {"run": staticmethod(run)})

    monkeypatch.setattr(cells, "traffic_kind", spying)
    result = _tiny_run(
        "pong_live",
        # a cap the CPU learner never reaches, so run() never sees a
        # learner that "can make no progress" and returns on its own
        ("replay.capacity=4096", "replay.segs_per_add=4",
         "learner.steps_per_frame_cap=1.0"),
        traffic={"clients": 4, "obs_pool": 64, "segment_pool": 8,
                 "fill_segments_per_message": 16, "settle_s": 0.5,
                 "settle_max_s": 0.5},
        seconds=4.0)
    _assert_result_line(result, "pong_live")
    ledger = facts["ingest_ledger"]
    assert ledger["offered"] == ledger["added"] + ledger["dropped"]
    assert ledger["offered"] > 4096
    for check in ("offered_is_added_plus_dropped",
                  "server_q_matches_reference", "tree_root_is_leaf_sum",
                  "no_loop_errors"):
        assert facts["checks"][check], check
    assert result["failed"] == 0


DP4_CHILD = """
import sys, json
sys.path.insert(0, {root!r})
sys.path.insert(0, {tests!r})
import test_run_cpu as t
r = t._tiny_run("atari57_dp4_offline", ("replay.capacity=16384",))
t._assert_result_line(r, "atari57_dp4_offline")
print(json.dumps({{"correct": r["correct"], "count": r["device"]["count"],
                  "failed": r["failed"]}}))
"""


def test_dp4_offline_tiny_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", DP4_CHILD.format(
            root=ROOT, tests=os.path.dirname(os.path.abspath(__file__)))],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out == {"correct": True, "count": 4, "failed": 0}
