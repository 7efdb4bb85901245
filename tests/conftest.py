"""Test harness: run everything on a virtual 8-device CPU mesh.

Multi-chip hardware is unavailable in CI; sharding/collective code is
exercised on XLA's host-platform device emulation (SURVEY.md §4
"distributed-without-a-cluster"). Env vars must be set before jax imports.
"""

import faulthandler
import os

# the full one-command suite has a known native-side SIGSEGV near the
# end of collection-order runs (ROADMAP.md "Tier-1 invocation"); dump
# Python tracebacks on fatal signals so the crashing test is
# attributable instead of a bare exit code 139
faulthandler.enable()

os.environ["JAX_PLATFORMS"] = "cpu"
# tests compile from scratch: entry points under test point JAX at the
# checkout's persistent compile cache (utils/compile_cache.py), and a
# test must not read what an earlier run left there
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

# every runtime lock is built via obs.health.make_lock; under this
# flag they become witness locks that record the lock-acquisition
# graph and raise LockOrderError the moment any test's code path
# acquires two locks in an order that closes a cycle — a deadlock
# that would otherwise need a precise interleave to reproduce
os.environ.setdefault("APEX_LOCK_WITNESS", "1")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

# compile-telemetry hook (obs/profiling.py): when run_chunked.sh
# exports APEX_COMPILE_LOG, each pytest process appends one JSON line
# {argv, jit_compiles, jit_compile_ms} at exit — the per-file
# compile-cache growth record that turns the chunking workaround's
# SIGSEGV regime into a monitored quantity
if os.environ.get("APEX_COMPILE_LOG"):
    from ape_x_dqn_tpu.obs.profiling import install_compile_log

    install_compile_log(os.environ["APEX_COMPILE_LOG"])


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Module-boundary jax.clear_caches() — the same fix
    runtime/suite.py:train_one_game applies between games. The full
    one-command suite accumulates compiled executables across ~200
    tests and reproducibly dies in native XLA teardown near the end of
    collection-order runs (ROADMAP.md 'Tier-1 invocation'); dropping
    the compilation caches at each test module's end keeps the
    native-side footprint bounded without perturbing any single
    module's warm-jit behavior."""
    yield
    import gc

    gc.collect()
    jax.clear_caches()


def pytest_addoption(parser):
    parser.addoption("--run-slow", action="store_true", default=False,
                     help="run slow integration tests (full CartPole solve)")


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration test")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--run-slow"):
        return
    skip = pytest.mark.skip(reason="needs --run-slow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)
