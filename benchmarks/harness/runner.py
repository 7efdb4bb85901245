"""One run of one cell: gate on the device, hand a `Runtime` to the
cell's traffic kind, reduce the trace, read the per-layer metrics,
print the result line.

The traffic kind (benchmarks/traffic_kinds/<kind>.py) owns building,
filling, warming, the measured window and the correctness checks of
its own system under test; it calls `rt.setup_done()` at the fence
that ends set-up, runs its window inside `rt.window(...)`, and returns
`facts`: a dict with `attempted`, `failed`, `checks` (name -> bool),
`end_to_end` (metric name -> value) and whatever counters and spans
the per-layer readers take their numbers from.
"""

from __future__ import annotations

import contextlib
import glob
import json
import math
import os
import threading
import time

from benchmarks.harness import cells as cells_mod
from benchmarks.harness import device as device_mod
from benchmarks.harness.cells import Cell
from benchmarks.harness.device import say

GIB = float(1 << 30)
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Runtime:
    """What a traffic kind gets from the harness."""

    def __init__(self, cell: Cell, seed: int, seconds: float,
                 trace: bool, t_process_start: float, devices: list,
                 cfg_overrides: tuple = ()):
        from ape_x_dqn_tpu.obs.profiling import CompileWatcher

        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = devices
        self.params = cell.traffic
        self.sizes = cell.config["sizes"]
        self._t0 = t_process_start
        self._cfg_overrides = tuple(cfg_overrides)
        self.setup_s: float | None = None
        self.watcher = CompileWatcher.install()
        self._compiles_at_start = self.watcher.snapshot()
        self._compiles_at_setup: tuple | None = None
        self._compiles_window: tuple[int, int] | None = None
        self.cache_hits = 0
        self.trace_dir = os.path.join(
            cells_mod.BENCH_DIR, ".trace", cell.name)

        import jax

        def on_event(event: str, **kw) -> None:
            if event == CACHE_HIT_EVENT:
                self.cache_hits += 1

        jax.monitoring.register_event_listener(on_event)

    # -- the configuration as the program takes it -----------------------

    def run_config(self):
        """The preset the configuration file names, with its overrides
        and --seed; widths are the preset's own (a benchmark test holds
        the file's `sizes` to them)."""
        from ape_x_dqn_tpu.configs import get_config
        from ape_x_dqn_tpu.runtime.train import apply_overrides

        cfg = get_config(self.cell.config["preset"])
        cfg = apply_overrides(cfg, list(self.cell.config["overrides"])
                              + list(self._cfg_overrides))
        return cfg.replace(seed=self.seed)

    # -- set-up / window bookkeeping -------------------------------------

    def setup_done(self) -> None:
        """Called at the fence that ends set-up (build, fill, warm-up,
        compile); everything before it is `setup_s`."""
        self.setup_s = time.monotonic() - self._t0
        self._compiles_at_setup = self.watcher.snapshot()

    @contextlib.contextmanager
    def window(self):
        """The measured window: counts compiles inside it (there must
        be none) and, in a traced run, profiles `trace_window_s` in its
        middle from a helper thread so the measured loop never blocks
        on the profiler."""
        n0, _ = self.watcher.snapshot()
        tracer = None
        if self.trace:
            tracer = threading.Thread(target=self._trace_middle,
                                      name="bench-tracer", daemon=True)
            tracer.start()
        try:
            yield
        finally:
            if tracer is not None:
                tracer.join()
            n1, _ = self.watcher.snapshot()
            self._compiles_window = (n0, n1)

    def _trace_middle(self) -> None:
        import jax

        width = min(float(self.params.get("trace_window_s", 1.0)),
                    self.seconds)
        time.sleep(max((self.seconds - width) / 2.0, 0.0))
        for old in glob.glob(os.path.join(self.trace_dir, "**",
                                          "*.xplane.pb"),
                             recursive=True):
            os.unlink(old)
        # the Python tracer records every call of ~40 threads: 30 MB
        # for two seconds of pong_live, and it slows the host it is
        # measuring. TraceAnnotation spans do not need it
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("bench.trace_window"):
                time.sleep(width)
        finally:
            jax.profiler.stop_trace()

    @property
    def compiles_in_window(self) -> int:
        n0, n1 = self._compiles_window
        return n1 - n0

    @property
    def setup_compile_s(self) -> float:
        return self._compiles_at_setup[1] - self._compiles_at_start[1]

    @property
    def setup_compiles(self) -> int:
        return self._compiles_at_setup[0] - self._compiles_at_start[0]

    def newest_xplane(self) -> str | None:
        found = glob.glob(os.path.join(self.trace_dir, "**",
                                       "*.xplane.pb"), recursive=True)
        return max(found, key=os.path.getmtime) if found else None


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process_start: float, devices: list,
             cfg_overrides: tuple = ()) -> dict:
    """-> the result object of the builder's contract (not printed)."""
    rt = Runtime(cell, seed, seconds, trace, t_process_start, devices,
                 cfg_overrides)
    kind = cells_mod.traffic_kind(cell)
    facts = kind.run(rt)
    if rt.setup_s is None or rt._compiles_window is None:
        raise RuntimeError(f"traffic kind {cell.traffic['kind']!r} "
                           f"never called setup_done()/window()")
    dev = device_mod.stamp(devices)
    facts["runtime"] = rt
    facts["setup"] = {"setup_s": rt.setup_s,
                      "compile_s": rt.setup_compile_s,
                      "compiles": rt.setup_compiles,
                      "cache_hits": rt.cache_hits}
    checks = dict(facts["checks"])
    checks["no_compile_in_window"] = rt.compiles_in_window == 0
    say(f"setup {rt.setup_s:.2f}s ({rt.setup_compiles} compiles, "
        f"{rt.setup_compile_s:.2f}s, {rt.cache_hits} cache hits); "
        f"compiles in window {rt.compiles_in_window}")
    say("checks " + json.dumps(checks))

    end_to_end = dict(facts["end_to_end"])
    end_to_end["setup_s"] = rt.setup_s
    end_to_end["peak_hbm_gib"] = dev["memory_peak_bytes"] / GIB
    say("end_to_end " + json.dumps(end_to_end))

    result = {"correct": all(checks.values()),
              "attempted": int(facts["attempted"]),
              "failed": int(facts["failed"]), "metrics": {},
              "device": dev}
    if not trace:
        for m in cell.end_to_end:
            value = end_to_end.get(m["name"])
            if not _finite(value):
                raise RuntimeError(
                    f"cell {cell.name!r} did not produce end-to-end "
                    f"metric {m['name']!r} (got {value!r})")
            result["metrics"][m["name"]] = {"value": value,
                                            "unit": m["unit"]}
        return result

    from benchmarks.harness import trace_reduce

    path = rt.newest_xplane()
    facts["trace"] = trace_reduce.reduce(path) if path else None
    if facts["trace"] is None:
        raise RuntimeError("traced run left no .xplane.pb to reduce")
    say("trace " + json.dumps(trace_reduce.summary(facts["trace"])))
    for m in cell.per_layer:
        value = cells_mod.layer_metric_reader(m["name"]).read(facts)
        # a reader that finds nothing to read returns nothing, and the
        # metric is left out of the line
        if _finite(value):
            result["metrics"][m["name"]] = {"value": value,
                                            "unit": m["unit"]}
    tr = facts["trace"]
    result["device"]["busy_s"] = tr["busy_s_mean"]
    result["device"]["window_s"] = tr["window_s"]
    result["breakdown"] = {"device_ops": tr["device_ops"][:10],
                           "idle_gaps": tr["idle_gaps"][:10]}
    return result


def main(workload: str, seed: int, seconds: float, trace: bool,
         t_process_start: float) -> int:
    cell = cells_mod.resolve(workload)
    # the program's own cache location: <checkout>/.jax_cache unless
    # JAX_COMPILATION_CACHE_DIR is set, so only a cell's first run in a
    # checkout compiles
    from ape_x_dqn_tpu.utils.compile_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    try:
        devices = device_mod.require_chips(cell.chips)
    except device_mod.NoChip as e:
        say(str(e))
        return 2
    say(f"cell {cell.name}: {len(devices)} x {devices[0].device_kind}; "
        f"seed {seed}, {seconds}s, trace {int(trace)}; compile cache "
        f"{cache_dir}")
    result = run_cell(cell, seed, seconds, trace, t_process_start,
                      devices)
    print(json.dumps(result), flush=True)
    return 0
