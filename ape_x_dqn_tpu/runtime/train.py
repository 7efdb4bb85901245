"""CLI entry point: launch any of the five configs from a shell.

SURVEY.md §1 layer 7 / §2.1: the reference ships per-config training
entry points; here one CLI selects a preset and overrides any field:

    python -m ape_x_dqn_tpu.runtime.train --config pong --actors 8 \
        --total-env-frames 1000000 --metrics-file run.jsonl
    python -m ape_x_dqn_tpu.runtime.train --config cartpole_smoke \
        --single-process --set learner.lr=5e-4

`--listen HOST:PORT` additionally accepts remote actor hosts
(runtime/actor_host.py) over the socket transport while local actors
(if any) keep running — the single-machine and multi-host topologies
share this entry point.

Prints one summary JSON line on stdout when the run ends.
"""

from __future__ import annotations

import argparse
import ast
import dataclasses
import json
import sys
from typing import Any

from ape_x_dqn_tpu.configs import PRESETS, RunConfig, get_config
from ape_x_dqn_tpu.utils.compile_cache import ensure_compile_cache
from ape_x_dqn_tpu.utils.metrics import Metrics, device_stamp


def _coerce(value: str, ref: Any) -> Any:
    """Parse a CLI string against the type of the value it replaces."""
    if value.lower() in ("none", "null"):
        return None  # optional fields can be cleared from the CLI
    if isinstance(ref, bool):  # before int: bool is an int subclass
        if value.lower() in ("1", "true", "yes", "on"):
            return True
        if value.lower() in ("0", "false", "no", "off"):
            return False
        raise ValueError(f"not a bool: {value!r}")
    if isinstance(ref, int):
        return int(value)
    if isinstance(ref, float):
        return float(value)
    if isinstance(ref, tuple):
        parsed = ast.literal_eval(value)
        return tuple(parsed) if isinstance(parsed, (list, tuple)) \
            else (parsed,)
    if ref is None:
        # the current value carries no type (e.g. `float | None` fields
        # like learner.steps_per_frame_cap): parse the literal itself, so
        # `--set learner.steps_per_frame_cap=1.0` lands as a float and
        # not the string '1.0' (which the learner loop would crash on)
        try:
            return ast.literal_eval(value)
        except (ValueError, SyntaxError):
            return value
    return value  # str fields


def _set_dotted(cfg: Any, path: list[str], value: str) -> Any:
    field_names = {f.name for f in dataclasses.fields(cfg)}
    head = path[0]
    if head not in field_names:
        raise KeyError(
            f"unknown config field {head!r}; known: {sorted(field_names)}")
    current = getattr(cfg, head)
    if len(path) == 1:
        return dataclasses.replace(cfg, **{head: _coerce(value, current)})
    return dataclasses.replace(
        cfg, **{head: _set_dotted(current, path[1:], value)})


def apply_overrides(cfg: RunConfig, sets: list[str]) -> RunConfig:
    """Apply 'dotted.path=value' overrides onto a (frozen) RunConfig."""
    for item in sets:
        if "=" not in item:
            raise ValueError(f"--set expects key=value, got {item!r}")
        key, value = item.split("=", 1)
        cfg = _set_dotted(cfg, key.split("."), value)
    return cfg


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m ape_x_dqn_tpu.runtime.train",
        description="Train any Ape-X config on TPU.")
    ap.add_argument("--config", required=True, choices=sorted(PRESETS),
                    help="preset name (SURVEY.md §2.1)")
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument("--actors", type=int, default=None,
                    help="override actors.num_actors")
    ap.add_argument("--total-env-frames", type=int, default=None)
    ap.add_argument("--max-grad-steps", type=int, default=10**9)
    ap.add_argument("--wall-clock-limit", type=float, default=None,
                    metavar="SECONDS")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--profile-dir", default=None,
                    help="capture a JAX profiler trace of the learner "
                         "hot loop into this directory")
    ap.add_argument("--metrics-file", default=None,
                    help="JSONL metrics sink")
    ap.add_argument("--tensorboard-dir", default=None,
                    help="also write TensorBoard event files here "
                         "(JSONL stays canonical; needs torch or "
                         "tensorboardX for the writer)")
    ap.add_argument("--eval-only", action="store_true",
                    help="no training: restore the latest checkpoint and "
                         "run greedy eval (the full HNS suite for Atari "
                         "configs); prints one JSON line")
    ap.add_argument("--games", default=None, metavar="G1,G2,...",
                    help="with --eval-only: comma-separated ALE games "
                         "(default: all 57)")
    ap.add_argument("--single-process", action="store_true",
                    help="config-1 style in-process loop (no threads)")
    ap.add_argument("--param-wire-dtype", default="bfloat16",
                    choices=("bfloat16", "float32"),
                    help="dtype for float params on the DCN wire with "
                         "--listen: bf16 halves the weight-broadcast "
                         "bytes (receivers upcast; values carry bf16 "
                         "rounding only); float32 is bit-exact")
    ap.add_argument("--listen", default=None, metavar="HOST:PORT",
                    help="also accept remote actor hosts over TCP")
    # multi-host learner (one process per host, SPMD lockstep over a
    # global mesh — runtime/multihost_driver.py); all three must be set
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                    help="jax.distributed coordinator address")
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--set", action="append", default=[],
                    metavar="dotted.key=value",
                    help="override any config field, e.g. "
                         "learner.batch_size=256 (repeatable)")
    return ap


def main(argv: list[str] | None = None) -> int:
    # before any backend compiles: resumed/preempted runs then load
    # the warm-up graphs instead of recompiling them
    ensure_compile_cache()
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.coordinator is not None:
        if args.num_processes is None or args.process_id is None:
            parser.error("--coordinator requires --num-processes and "
                         "--process-id")
        if args.wall_clock_limit is not None:
            # wall clocks differ across hosts (they would diverge the
            # lockstep call sequences) — reject rather than silently
            # ignore
            parser.error("--wall-clock-limit is not supported in "
                         "multihost mode (host clocks differ; use "
                         "--max-grad-steps / --total-env-frames)")
        if args.single_process:
            parser.error("--single-process and --coordinator conflict")
        # must happen before any JAX backend use
        from ape_x_dqn_tpu.parallel.multihost import init_multihost
        init_multihost(args.coordinator, args.num_processes,
                       args.process_id)
    cfg = get_config(args.config)
    if args.seed is not None:
        cfg = cfg.replace(seed=args.seed)
    if args.actors is not None:
        cfg = cfg.replace(
            actors=dataclasses.replace(cfg.actors, num_actors=args.actors))
    if args.total_env_frames is not None:
        cfg = cfg.replace(total_env_frames=args.total_env_frames)
    if args.checkpoint_dir is not None:
        cfg = cfg.replace(checkpoint_dir=args.checkpoint_dir)
    if args.profile_dir is not None:
        cfg = cfg.replace(profile_dir=args.profile_dir)
    cfg = apply_overrides(cfg, args.set)

    if args.eval_only:
        if args.coordinator is not None:
            parser.error("--eval-only is single-process (no learner "
                         "mesh); drop --coordinator")
        from ape_x_dqn_tpu.runtime.evaluation import run_suite_eval
        out = run_suite_eval(
            cfg, games=args.games.split(",") if args.games else None,
            checkpoint_dir=args.checkpoint_dir or cfg.checkpoint_dir
            or None)
        print(json.dumps(out))
        return 0

    metrics = Metrics(log_path=args.metrics_file,
                      tensorboard_dir=args.tensorboard_dir)
    transport = server = None
    if args.listen and not args.single_process:
        from ape_x_dqn_tpu.comm.socket_transport import SocketIngestServer
        host, port = args.listen.rsplit(":", 1)
        server = transport = SocketIngestServer(
            host, int(port), param_wire_dtype=args.param_wire_dtype,
            wire_codec=cfg.comm.wire_codec,
            param_codec=getattr(cfg.comm, "param_codec", "delta-q8"),
            param_delta_window=getattr(cfg.comm, "param_delta_window", 8),
            shm=getattr(cfg.comm, "shm", False),
            shm_slots=getattr(cfg.comm, "shm_slots", 8),
            shm_slot_bytes=getattr(cfg.comm, "shm_slot_bytes", 1 << 22),
            shm_param_bytes=getattr(cfg.comm, "shm_param_bytes", 1 << 26))
        print(f"ingest listening on {host}:{server.port}",
              file=sys.stderr, flush=True)
    if args.coordinator is not None:
        from ape_x_dqn_tpu.runtime.multihost_driver import (
            MultihostApexDriver)
        driver = MultihostApexDriver(cfg, metrics=metrics,
                                     transport=transport)
        try:
            out = driver.run(max_grad_steps=args.max_grad_steps)
        finally:
            if server is not None:
                server.stop()
    elif args.single_process:
        from ape_x_dqn_tpu.runtime.single_process import train_single_process
        out = train_single_process(cfg, metrics=metrics)
    else:
        from ape_x_dqn_tpu.runtime.driver import ApexDriver
        driver = ApexDriver(cfg, metrics=metrics, transport=transport)
        try:
            out = driver.run(max_grad_steps=args.max_grad_steps,
                             wall_clock_limit_s=args.wall_clock_limit)
        finally:
            if server is not None:
                server.stop()
        # summary must stay one parseable JSON line
        out = dict(out)
        out["actor_errors"] = [f"{i}: {e!r}"
                               for i, e in out["actor_errors"]]
        out["loop_errors"] = [f"{which}: {e!r}"
                              for which, e in out["loop_errors"]]
    metrics.close()
    # the summary names the device its numbers came from
    out = {**out, **device_stamp()}
    print(json.dumps(out))
    if out.get("grad_steps") == 0:
        # e.g. the wall-clock limit expired during fill: a training run
        # that never took a step has not succeeded
        print("train: run ended with 0 grad steps", file=sys.stderr)
        return 1
    failed = bool(out.get("actor_errors") or out.get("loop_errors"))
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
