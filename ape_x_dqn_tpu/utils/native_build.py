"""Shared lazy build-and-load for the native C++ components.

The runtime's native pieces (cpp/framing.cpp wire codec, cpp/preproc.cpp
observation kernel) compile with g++ on first use and cache the .so next
to the source. The .so is named by a hash of the source bytes and the
full compiler command line (plus `machine_tag()` for -march=native
builds), so a binary is only ever loaded if it was built from exactly
this source with exactly these flags: one copied in from another
checkout, or left behind by an older source, has another name and is
never looked at. A host WITHOUT g++ gets None and the callers use their
numpy/zlib paths, which are wire/bit compatible; with g++ present a
failed build is a build break and raises.

This module owns the concurrency-sensitive scaffolding once — per-pid
temp + atomic rename (concurrent first use across processes must not
cache a corrupt .so), temp cleanup on failed/timed-out compiles,
one-shot caching — so the per-component bindings don't each
re-implement it.

Every build runs with -Wall -Wextra -Werror: the native modules are
small enough that zero-warning is cheap to hold, and a warning in a
memcpy/pointer-arithmetic data plane is usually a bug report.

APEX_NATIVE_SANITIZE=1 additionally compiles with
-fsanitize=address,undefined for local debugging runs (the flags are
part of the name hash, so a sanitized build never serves a normal run).
Loading one into a non-ASan python needs the process launched with the
runtime preloaded (or the ASan link-order check relaxed), e.g.:

    LD_PRELOAD=$(gcc -print-file-name=libasan.so) \
        APEX_NATIVE_SANITIZE=1 python ...
    # or: ASAN_OPTIONS=verify_asan_link_order=0 APEX_NATIVE_SANITIZE=1 ...

When neither is set the sanitized .so is still BUILT (so the compile
gate runs) but not loaded — the callers fall back to the pure-Python
paths with a one-line stderr warning instead of ASan aborting the
process at dlopen.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import shutil
import subprocess
import threading

_lock = threading.Lock()
_cache: dict[str, ctypes.CDLL | None] = {}  # guarded-by: _lock

# the data plane must stay warning-clean; -Werror keeps it honest
WARNING_FLAGS = ("-Wall", "-Wextra", "-Werror")
SANITIZE_FLAGS = ("-fsanitize=address,undefined", "-fno-omit-frame-pointer")


def _sanitize() -> bool:
    return os.environ.get("APEX_NATIVE_SANITIZE", "") not in ("", "0")


def _so_path(src: str, name: str, flags: tuple[str, ...]) -> str:
    """`<src dir>/<name>.<hash of source bytes + flags>[.<machine>].so`"""
    with open(src, "rb") as fh:
        digest = hashlib.sha256(
            fh.read() + "\0".join(flags).encode()).hexdigest()[:12]
    tag = f".{machine_tag()}" if "-march=native" in flags else ""
    return os.path.join(os.path.dirname(src), f"{name}.{digest}{tag}.so")


def build_and_load(src: str, name: str,
                   flags: tuple[str, ...] = ()) -> ctypes.CDLL | None:
    """Compile src with g++ (unless this exact build exists) and dlopen
    it.

    Returns None only when the host has no g++ (callers fall back to
    their pure-Python implementations) or for an unloadable sanitized
    build; a compile that fails raises. The result (including None) is
    cached per so-path for the process lifetime.
    """
    flags = ("-O3", *WARNING_FLAGS, *flags)
    load_ok = True
    if _sanitize():
        flags += SANITIZE_FLAGS
        # dlopen'ing an ASan .so into a python that wasn't started with
        # the runtime preloaded (or the link-order check relaxed) makes
        # the ASan init ABORT the whole process — and it snapshots the
        # environment before python code runs, so this cannot be fixed
        # from here. Build the artifact (so -Werror + sanitizer compile
        # checks still gate), but only load it when the process was
        # launched prepared; otherwise warn once and fall back.
        load_ok = (
            "asan" in os.environ.get("LD_PRELOAD", "")
            or "verify_asan_link_order=0" in os.environ.get(
                "ASAN_OPTIONS", ""))
        if not load_ok:
            import sys
            print(
                "[native-build] APEX_NATIVE_SANITIZE=1 but the ASan "
                "runtime is not loadable in this process; building "
                f"{name} but using the Python fallback. Relaunch with "
                "LD_PRELOAD=$(gcc -print-file-name=libasan.so) or "
                "ASAN_OPTIONS=verify_asan_link_order=0.",
                file=sys.stderr)
    so = _so_path(src, name, flags)
    with _lock:
        if so in _cache:
            return _cache[so]
        if not os.path.exists(so):
            if shutil.which("g++") is None:
                _cache[so] = None
                return None
            tmp = f"{so}.{os.getpid()}"
            try:
                proc = subprocess.run(
                    ["g++", *flags, "-shared", "-fPIC", src, "-o", tmp],
                    capture_output=True, text=True, timeout=120)
                if proc.returncode != 0:
                    raise RuntimeError(
                        f"native build of {os.path.basename(src)} failed "
                        f"(g++ exit {proc.returncode}):\n"
                        f"{proc.stderr[-4000:]}")
                os.replace(tmp, so)
            finally:
                try:
                    os.unlink(tmp)  # leftover of a failed/killed compile
                except OSError:
                    pass
        lib = ctypes.CDLL(so) if load_ok else None
        _cache[so] = lib
        return lib


def machine_tag() -> str:
    """Stable per-CPU-model tag for arch-specific builds.

    -march=native binaries cached on a shared filesystem (NFS home,
    cluster checkout, a tree copied to another machine) would SIGILL
    on hosts with a different ISA — CDLL succeeds, so nothing catches
    it. Embedding this tag in the .so name gives identical CPUs a
    shared cache and everything else its own build.
    """
    try:
        with open("/proc/cpuinfo") as fh:
            # x86 keys plus their ARM equivalents; frequency lines vary
            # run to run and must stay out of the hash
            lines = {ln for ln in fh
                     if ln.startswith(("model name", "flags", "Features",
                                       "CPU implementer", "CPU part"))}
        if not lines:
            raise OSError("no ISA-identifying cpuinfo lines")
        return hashlib.md5("".join(sorted(lines)).encode()).hexdigest()[:8]
    except OSError:
        return platform.machine() or "generic"
