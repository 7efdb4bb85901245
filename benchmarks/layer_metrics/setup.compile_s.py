"""Seconds the backend spent compiling (or loading from the persistent
cache) during set-up, from the program's `CompileWatcher` — the part
of `setup_s` a warm `.jax_cache` removes."""


def read(facts: dict) -> float | None:
    return facts["setup"]["compile_s"]
