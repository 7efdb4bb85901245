"""The device gate and stamp. A measurement path that finds no chip
fails; it never falls back to the CPU."""

from __future__ import annotations

import sys
import time

from benchmarks.harness.peaks import PEAKS


class NoChip(RuntimeError):
    """JAX found something other than the chips the cell asks for."""


def require_chips(chips: int) -> list:
    """The cell's devices, or NoChip: the platform must be `tpu`, the
    device kind must be in the peaks table, and the machine must hold
    exactly the chips the cell asks for (the driver refuses a result
    stamped with another count)."""
    import jax

    found = jax.devices()
    kind = found[0].device_kind
    if found[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found platform="
                     f"{found[0].platform!r} device_kind={kind!r} "
                     f"count={len(found)}")
    if kind not in PEAKS:
        raise NoChip(f"device_kind {kind!r} is not in the peaks table "
                     f"({sorted(PEAKS)})")
    if len(found) != chips:
        raise NoChip(f"cell needs {chips} chip(s); JAX found "
                     f"{len(found)}")
    return found


def stamp(devices: list) -> dict:
    """`device` of the result line, as JAX reports it;
    memory_peak_bytes is the peak on the fullest chip."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peaks)}


_T0 = time.monotonic()


def say(msg: str) -> None:
    """Progress lines go to stderr, stamped with the seconds since the
    harness was imported: stdout's last line is the result."""
    print(f"[bench {time.monotonic() - _T0:7.2f}s] {msg}",
          file=sys.stderr, flush=True)
