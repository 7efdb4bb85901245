"""ctypes bindings for the native Atari observation kernel
(cpp/preproc.cpp).

Compiled lazily via utils/native_build.py; on a host without g++,
preproc() returns None and envs/atari.py uses the numpy pipeline,
which is numerically identical (tests/test_envs.py asserts
bit-equality) — just slower, since it materializes per-frame float
intermediates.

Flags: -march=native is safe AND load-bearing (~1.7x; the .so name
carries a per-CPU-model tag so a shared checkout never serves a
wrong-ISA binary); -ffp-contract=off keeps numpy bit-parity — the
kernel mirrors numpy's discrete float operations, and a fused
multiply-add would round differently.
"""

from __future__ import annotations

import ctypes
import os

import numpy as np

from ape_x_dqn_tpu.utils.native_build import build_and_load

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "cpp", "preproc.cpp")


_lib: ctypes.CDLL | None = None
_tried = False


def _load() -> ctypes.CDLL | None:
    # module-level cache: preproc() runs once per env step in every
    # actor thread, so it must not re-enter build_and_load's global
    # lock or rebind argtypes per frame (benign if two threads race
    # the first call — the work is idempotent)
    global _lib, _tried
    if _tried:
        return _lib
    lib = build_and_load(_SRC, "libapex_preproc",
                         flags=("-march=native", "-ffp-contract=off"))
    if lib is not None:
        lib.apex_preproc.restype = None
        lib.apex_preproc.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_uint64, ctypes.c_uint64,
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64]
    _lib, _tried = lib, True
    return _lib


def available() -> bool:
    return _load() is not None


def preproc(f0: np.ndarray, f1: np.ndarray | None,
            out_h: int, out_w: int) -> np.ndarray | None:
    """max(f0, f1) -> grayscale -> bilinear (out_h, out_w) -> uint8.

    f0/f1: uint8 [H, W, 3] RGB (f1 None = single frame). Returns None
    when the native library is unavailable (caller falls back to
    numpy).
    """
    lib = _load()
    if lib is None:
        return None
    f0 = np.ascontiguousarray(f0, np.uint8)
    p1 = None
    if f1 is not None:
        f1 = np.ascontiguousarray(f1, np.uint8)
        p1 = f1.ctypes.data_as(ctypes.c_void_p)
    h, w = f0.shape[:2]
    out = np.empty((out_h, out_w), np.uint8)
    lib.apex_preproc(f0.ctypes.data_as(ctypes.c_void_p), p1, h, w,
                     out.ctypes.data_as(ctypes.c_void_p), out_h, out_w)
    return out
