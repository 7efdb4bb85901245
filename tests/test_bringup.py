"""Chip bring-up guards (ISSUE 21): the things that would let a chip
run pass without the chip, or fail for the wrong reason, checked on the
CPU — chip_smoke.py refuses anything but a TPU, the compile cache is
placed from outside, and a native .so is only ever loaded if it was
built from exactly the source on disk."""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, cwd, env_extra=None, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env["PYTHONPATH"] = REPO
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *argv], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_chip_smoke_refuses_cpu(tmp_path):
    """No CPU mode: off the chip the smoke names the platform JAX found,
    exits non-zero within seconds and prints no result."""
    proc = _run([os.path.join(REPO, "chip_smoke.py")], str(tmp_path),
                {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "platform='cpu'" in proc.stderr
    assert proc.stdout.strip() == ""


def test_chip_smoke_last_line_keys():
    """The chip check reads the last stdout line and refuses any key
    beyond ok / device{platform, kind, count}; the full result is the
    line before it."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    header = {"platform": "tpu", "device_kind": "TPU v5 lite",
              "device_count": 1.0, "run_name": "pong"}
    assert chip_smoke.verdict_line(header, []) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    bad = chip_smoke.verdict_line(header, ["grad_steps"])
    assert bad["ok"] is False and set(bad) == {"ok", "device"}
    assert isinstance(bad["device"]["count"], int)


_PRINT_CACHE_DIR = (
    "from ape_x_dqn_tpu.utils.compile_cache import ensure_compile_cache;"
    "d = ensure_compile_cache(); import jax;"
    "print(d); print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_left_to_the_environment(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it, the helper sets
    nothing of its own."""
    want = str(tmp_path / "outside")
    proc = _run(["-c", _PRINT_CACHE_DIR], str(tmp_path),
                {"JAX_COMPILATION_CACHE_DIR": want})
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [want, want]


def test_compile_cache_fixed_path_from_any_cwd(tmp_path):
    """Unset: <checkout>/.jax_cache, derived from the package location —
    the same from two working directories (the path is part of JAX's
    cache key, so a directory that moves never hits)."""
    other = tmp_path / "elsewhere"
    other.mkdir()
    outs = [_run(["-c", _PRINT_CACHE_DIR], cwd,
                 drop=("JAX_COMPILATION_CACHE_DIR",))
            for cwd in (str(tmp_path), str(other))]
    for proc in outs:
        assert proc.returncode == 0, proc.stderr
    want = os.path.join(REPO, ".jax_cache")
    assert outs[0].stdout.split() == [want, want]
    assert outs[1].stdout == outs[0].stdout


@pytest.mark.skipif(shutil.which("g++") is None, reason="needs g++")
def test_native_so_follows_source_hash(tmp_path):
    """A .so is named by the hash of its source bytes and flags, so a
    binary built from other source — older, or copied in from another
    checkout — has another name and is rebuilt past, never loaded; and
    with a compiler present a failed build raises."""
    from ape_x_dqn_tpu.utils.native_build import build_and_load

    src = tmp_path / "answer.cpp"
    src.write_text('extern "C" int answer() { return 1; }\n')
    lib1 = build_and_load(str(src), "libanswer")
    assert lib1.answer() == 1
    built1 = sorted(p.name for p in tmp_path.glob("*.so"))
    assert len(built1) == 1
    # plant the old binary under the pre-hash name and under a newer
    # mtime than the source: neither may ever be picked up
    shutil.copy(tmp_path / built1[0], tmp_path / "libanswer.so")
    src.write_text('extern "C" int answer() { return 2; }\n')
    os.utime(tmp_path / built1[0])
    lib2 = build_and_load(str(src), "libanswer")
    assert lib2.answer() == 2
    built2 = {p.name for p in tmp_path.glob("*.so")}
    assert len(built2 - set(built1) - {"libanswer.so"}) == 1
    src.write_text('extern "C" int answer() { return }\n')
    with pytest.raises(RuntimeError, match="native build of answer.cpp"):
        build_and_load(str(src), "libanswer")
