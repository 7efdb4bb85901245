#!/usr/bin/env python3
"""Chip smoke: the `pong` preset end to end on one TPU chip.

    python3 chip_smoke.py

Drives the system's main path once through the entry point users call,
`ape_x_dqn_tpu.runtime.train.main`, in this process (one process per
chip), at the preset's full device widths: dueling Nature-CNN, batch
512, sample_chunk 4, train_chunk 8, the 2^20-transition frame-ring
replay with its sum-tree in HBM, and the batched inference server on
the chip answering 8 vector actors x 16 envs. Only the run's length is
bounded (64 grad steps, one short end-of-run eval). Weights are random
from the preset's seed; the environment is whatever `make_env` builds
here (printed below).

There is no CPU mode. On anything but a TPU the script names what JAX
found and exits non-zero without a result; rehearse on the CPU by
calling `python -m ape_x_dqn_tpu.runtime.train` directly with small
`--set` overrides (.claude/skills/verify/SKILL.md has the command).

The last two stdout lines are JSON objects. The second to last is the
full result: every checked fact and the unjudged numbers (compile
seconds and count, peak HBM, drops, server batch fill); it is also
written to chiprun_out/chip_smoke_result.json. The last is exactly
`{"ok": ..., "device": {"platform", "kind", "count"}}`, the device as
the run's own header reported it. Any exception or failed check exits
non-zero.
"""

from __future__ import annotations

import contextlib
import importlib.metadata
import io
import json
import math
import os
import sys
import time

CONFIG = "pong"
GRAD_STEPS = 64
WALL_CLOCK_LIMIT_S = 900
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def verdict_line(header: dict, failed: list[str]) -> dict:
    """The last stdout line: exactly these keys, nothing else, so a
    reader of the tail needs no knowledge of the full result."""
    return {
        "ok": not failed,
        "device": {"platform": str(header["platform"]),
                   "kind": str(header["device_kind"]),
                   "count": int(header["device_count"])},
    }


def main() -> int:
    t_start = time.monotonic()
    from ape_x_dqn_tpu.utils.compile_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    import jax
    import jaxlib

    found = jax.devices()
    if found[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform="
              f"{found[0].platform!r} device_kind="
              f"{found[0].device_kind!r} count={len(found)}",
              file=sys.stderr)
        return 2

    from ape_x_dqn_tpu.comm import native as framing_native
    from ape_x_dqn_tpu.configs import get_config
    from ape_x_dqn_tpu.envs import native as preproc_native
    from ape_x_dqn_tpu.envs.atari import atari_backend
    from ape_x_dqn_tpu.obs.profiling import CompileWatcher
    from ape_x_dqn_tpu.runtime import train

    cfg = get_config(CONFIG)
    native = {"framing": framing_native.have_native(),
              "preproc": preproc_native.available()}
    cache_entries_at_start = (len(os.listdir(cache_dir))
                              if os.path.isdir(cache_dir) else 0)
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": importlib.metadata.version("libtpu")}
    env_backend = atari_backend(cfg.env.kind)
    print(f"chip_smoke: {len(found)} x {found[0].device_kind} "
          f"({found[0].platform}); {versions}")
    print(f"chip_smoke: env backend {env_backend}; native {native}; "
          f"compile cache {cache_dir} "
          f"({cache_entries_at_start} entries at start)", flush=True)

    watcher = CompileWatcher.install()
    compiles0, compile_s0 = watcher.snapshot()
    cache_hits = [0]

    def on_event(event: str, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            cache_hits[0] += 1

    jax.monitoring.register_event_listener(on_event)

    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    jsonl = os.path.join(out_dir, "chip_smoke.jsonl")
    if os.path.exists(jsonl):
        os.unlink(jsonl)  # Metrics appends; one run per file
    stdout = io.StringIO()
    with contextlib.redirect_stdout(stdout):
        rc = train.main([
            "--config", CONFIG,
            "--max-grad-steps", str(GRAD_STEPS),
            "--wall-clock-limit", str(WALL_CLOCK_LIMIT_S),
            "--metrics-file", jsonl,
            # the default end-of-run eval backstop is 10 episodes of up
            # to 108k frames; one bounded episode still drives the eval
            # worker through the server's single-query path
            "--set", "eval_episodes=1",
            "--set", "eval_max_frames=2000",
        ])
    sys.stdout.write(stdout.getvalue())
    summary = json.loads(stdout.getvalue().strip().splitlines()[-1])
    with open(jsonl) as fh:
        records = [json.loads(line) for line in fh]
    header = next(r for r in records if "run_name" in r)
    hbm = next(r for r in records if "hbm_limit_source" in r)
    warmup_skipped = any("warmup_skipped" in r
                         or "server_warmup_skipped" in r for r in records)
    compiles, compile_s = watcher.snapshot()
    compiles -= compiles0
    compile_s -= compile_s0
    wall_s = time.monotonic() - t_start
    memory = summary["device_memory"][0] or {}
    loss = summary["loss"]

    checks = {
        "train_exit_0": rc == 0,
        "header_platform_tpu": header["platform"] == "tpu",
        "grad_steps": summary["grad_steps"] >= GRAD_STEPS,
        "frames_ge_min_fill": summary["frames"] >= cfg.replay.min_fill,
        "server_batches": summary["server"]["batches"] > 0,
        "no_actor_errors": not summary["actor_errors"],
        "no_loop_errors": not summary["loop_errors"],
        "no_actor_restarts": not summary["actor_restarts"],
        "loss_finite": loss is not None and math.isfinite(loss),
        "no_warmup_skipped": not warmup_skipped,
        "params_version": summary["params_version"] > 0,
        "state_on_tpu": summary["state_platforms"] == ["tpu"],
        # "table" means this libtpu's memory_stats() gave no limit and
        # the fits-check used utils/hbm.KNOWN_HBM_BYTES — reported, not
        # failed; anything else means the check was not enforced
        "hbm_limit_known": hbm["hbm_limit_source"] in ("memory_stats",
                                                       "table"),
        "native_framing": native["framing"],
        "native_preproc": native["preproc"],
    }
    failed = sorted(k for k, ok in checks.items() if not ok)
    for k in failed:
        print(f"chip_smoke: FAILED check {k}", file=sys.stderr)

    verdict = verdict_line(header, failed)
    result = {
        **verdict,
        "failed_checks": failed,
        "platform": header["platform"],
        "device_kind": header["device_kind"],
        "versions": versions,
        "config": header["run_name"],
        "env_backend": env_backend,
        "batch_size": int(header["batch_size"]),
        "sample_chunk": int(header["sample_chunk"]),
        "train_chunk": int(header["train_chunk"]),
        "replay_capacity": int(hbm["replay_capacity_allocated"]),
        "grad_steps": summary["grad_steps"],
        "frames": summary["frames"],
        "loss": loss,
        "server_batches": summary["server"]["batches"],
        "server_avg_batch": round(summary["server"]["avg_batch"], 2),
        "params_version": summary["params_version"],
        "actor_errors": summary["actor_errors"],
        "loop_errors": summary["loop_errors"],
        "actor_restarts": summary["actor_restarts"],
        "warmup_skipped": warmup_skipped,
        "state_platforms": summary["state_platforms"],
        "native": native,
        "hbm_limit_source": hbm["hbm_limit_source"],
        "hbm_limit_bytes": hbm["hbm_limit_bytes"],
        "hbm_budget_bytes": hbm["hbm_budget_bytes"],
        "peak_bytes_in_use": memory.get("peak_bytes_in_use"),
        "bytes_in_use": memory.get("bytes_in_use"),
        "ingest_dropped": summary["ingest_dropped"],
        "eval": summary["eval"],
        "wall_s": round(wall_s, 1),
        "compile_s": round(compile_s, 1),
        "rest_s": round(wall_s - compile_s, 1),
        "run_wall_s": round(summary["wall_s"], 1),
        "compiles": compiles,
        "cache_hits": cache_hits[0],
        "cache_dir": cache_dir,
        "cache_entries_at_start": cache_entries_at_start,
    }
    with open(os.path.join(out_dir, "chip_smoke_result.json"), "w") as fh:
        json.dump(result, fh)
    print(json.dumps(result))
    print(json.dumps(verdict), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
