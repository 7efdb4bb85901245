"""Where the persistent XLA compilation cache lives.

Every entry point (runtime/train.py, runtime/actor_host.py,
chip_smoke.py, benchmarks/harness/runner.py) calls `ensure_compile_cache()` first thing, before any
backend compiles. The directory is decided from outside the program:

- `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself and this module
  touches nothing — whoever provisioned the machine owns the location.
- unset: `<checkout>/.jax_cache`, derived from this package's own
  location. The path is part of JAX's cache key, so it must be the same
  for every process of a checkout and must never come from a temp
  directory, a pid or the clock (a directory that moves never hits).
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def ensure_compile_cache() -> str:
    """Point JAX at the persistent compilation cache; returns the
    directory in use."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
