#!/usr/bin/env python3
"""One cell of BENCHMARK.json, once, in one process that holds the
cell's chips:

    python3 benchmarks/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Progress goes to stderr. The last line of stdout is the one JSON
object of the contract (`correct`, `attempted`, `failed`, `metrics`,
`device`, and `breakdown` when traced). There is no CPU mode: on
anything but the chips the cell asks for the exit code is non-zero and
no result is printed. benchmarks/README.md says how the pieces fit.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    from benchmarks.harness import runner

    return runner.main(args.workload, args.seed, args.seconds,
                       bool(args.trace), T_PROCESS_START)


if __name__ == "__main__":
    # os._exit after flushing: daemon threads of the system under test
    # (server, driver loops) are stopped by the traffic kind, and a
    # stuck interpreter teardown must not turn a finished run into a
    # timeout
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
