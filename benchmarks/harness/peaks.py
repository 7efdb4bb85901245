"""Published peaks of the chips this benchmark runs on, keyed by the
`device_kind` string JAX reports. A device that is not here is an
error, never a default: a utilisation over a made-up peak is worse
than none.

Source: Google Cloud documentation, "TPU v5e" (system architecture
page): 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s ICI per
chip. JAX reports the chip as "TPU v5 lite"; "TPU v5e" is the same
chip under the name newer runtimes give it.
"""

from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    bf16_flops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: float


_V5E = Peaks(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9,
             hbm_bytes=16e9)

PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device_kind {device_kind!r} is not in the benchmark's "
            f"peaks table ({sorted(PEAKS)}); add it with its source "
            f"in benchmarks/harness/peaks.py") from None
