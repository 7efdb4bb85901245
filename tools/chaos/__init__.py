"""Chaos lane: fault injection for the elastic fleet runtime.

The robustness claims of the transport/runtime layers (supervised
reconnect, membership epochs, fleet supervisor, byzantine-frame
accounting) are only claims until something actually breaks mid-run.
This package is the breaking side of that contract — deliberately
dependency-free and OUTSIDE ape_x_dqn_tpu/ (it is test/ops tooling,
not runtime code):

- `proxy.ChaosProxy`: a byte-level TCP forwarder that sits between an
  actor host and the learner's ingest port and injects wire faults on
  command — drop a fraction of chunks, delay them, truncate them
  mid-stream, garble payload bytes, or cut every live connection at
  once (the "learner blip" every reconnect test needs). Byte-level on
  purpose: it never parses frames, so it exercises the REAL decode
  paths with realistic mid-frame damage instead of polite
  message-boundary faults.

- `faults`: process/thread/frame fault helpers — SIGKILL a peer
  process, wedge a thread (holds it in a sleep loop until released),
  build corrupted wire frames (bad magic / bad crc / truncated / bit-
  flipped payload) for fuzzing a server's reader.

- CLI: `python -m tools.chaos --listen PORT --connect HOST:PORT
  [--drop R] [--delay S] [--truncate R] [--garble R]` runs a
  standalone proxy for manual soaks.

tests/test_chaos.py drives all of it as the chaos soak (fast variants
tier-1, full soak slow-marked).
"""

from tools.chaos.faults import (CORRUPTION_MODES, corrupt_frame, garble,
                                kill_process, truncate, ThreadWedge)
from tools.chaos.proxy import ChaosProxy

__all__ = ["ChaosProxy", "CORRUPTION_MODES", "ThreadWedge",
           "corrupt_frame", "garble", "kill_process", "truncate"]
