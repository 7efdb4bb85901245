"""Traffic kind `learner_free_run`: no actors, no server traffic, no
ingest. The ring is filled on the device during set-up from the seed;
the window dispatches `learner.train_many(state, train_chunk)` back to
back, exactly as `ApexDriver._learner_loop_inner` does with obs off:
no per-dispatch block, a bounded number of dispatches in flight so the
closing fence is exact.

The learner and its state are the program's own, built the way the
program builds them (`ApexDriver(cfg)`: family_setup, HBM fits-check,
SingleChipLearner on one chip, DistDQNLearner over the dp mesh on
four); the driver is never `run()`.

Parameters (benchmarks/traffic/<mix>.json): `ring_fill` (share of the
ring filled), `fill_segments_per_add`, `priority_lognormal_sigma`,
`terminal_one_in`, `max_dispatches_in_flight`, `trace_window_s`.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ape_x_dqn_tpu.runtime.driver import ApexDriver
from benchmarks.harness import learner_checks
from benchmarks.harness import ring_content as rc
from benchmarks.harness.device import say


def _fill(rt, driver, state, content: rc.Content, shard_segs: int):
    """Fill `ring_fill` of every shard with seeded segments generated
    on the device, through the program's own `learner.add`.
    -> (state, transitions written, fenced seconds)."""
    learner, dp = driver.learner, driver.dp
    want = int(shard_segs * float(rt.params["ring_fill"]))
    g = min(int(rt.params["fill_segments_per_add"]), want)

    def block(first):
        # shard d, ring slot k holds global segment d * shard_segs + k
        ids = first + jnp.arange(g, dtype=jnp.int32)
        if driver.is_dist:
            ids = ids[None, :] + (jnp.arange(dp, dtype=jnp.int32)
                                  * shard_segs)[:, None]
        return rc.segments(jnp, content, ids)

    if driver.is_dist:
        gen = jax.jit(block, out_shardings=NamedSharding(driver.mesh,
                                                         P("dp")))
    else:
        gen = jax.jit(block)
    t0 = time.monotonic()
    for c in range(want // g):
        items = gen(jnp.int32(c * g))
        pri = items.pop("priorities")
        state = learner.add(state, items, pri)
    jax.block_until_ready(state.replay.size)
    return (state, (want // g) * g * content.geom.seg * dp,
            time.monotonic() - t0)


def run(rt) -> dict:
    cfg = rt.run_config()
    say("imports done; building ApexDriver")
    driver = ApexDriver(cfg)
    try:
        return _run(rt, cfg, driver)
    finally:
        driver.server.stop()   # the only thread the constructor starts


def _run(rt, cfg, driver) -> dict:
    learner, state = driver.learner, driver.state
    content = rc.Content(rc.geometry(cfg, driver.spec), rt.seed,
                         float(rt.params["priority_lognormal_sigma"]),
                         int(rt.params["terminal_one_in"]))
    seg = content.geom.seg
    shard_segs = driver.capacity // driver.dp // seg
    chunk = max(min(cfg.learner.train_chunk, cfg.learner.publish_every), 1)
    say(f"learner {type(learner).__name__}, ring {driver.capacity} "
        f"transitions over dp={driver.dp}, batch "
        f"{cfg.learner.batch_size}, train_chunk {chunk}")

    state, filled, fill_s = _fill(rt, driver, state, content,
                                  shard_segs)
    say(f"filled {filled} transitions in {fill_s:.2f}s")
    # warm the one graph the window uses (compiles on a cell's first
    # run in a checkout, loads from .jax_cache afterwards)
    state, m = learner.train_many(state, chunk)
    jax.block_until_ready(m["loss"])
    say("train_many warm")
    rt.setup_done()

    max_in_flight = int(rt.params["max_dispatches_in_flight"])
    losses = []
    annotate = jax.profiler.TraceAnnotation
    with rt.window():
        t0 = time.monotonic()
        deadline = t0 + rt.seconds
        while time.monotonic() < deadline:
            with annotate("bench.train_dispatch"):
                state, m = learner.train_many(state, chunk)
            losses.append(m["loss"])
            if len(losses) > max_in_flight:
                with annotate("bench.wait_in_flight"):
                    losses[-1 - max_in_flight].block_until_ready()
        with annotate("bench.closing_fence"):
            jax.block_until_ready(m["loss"])
        window_s = time.monotonic() - t0

    steps = len(losses) * chunk
    losses = np.asarray(jax.device_get(losses))
    bad_dispatches = int((~np.isfinite(losses)).sum())
    state, checks, notes = learner_checks.check_learner(
        learner, state, cfg, driver.dp, rt.sizes["cnn_strides"],
        # what the fill wrote at (shard, shard-local transition index)
        lambda shard, local, items: rc.expected_transitions(
            content, shard * shard_segs + local // seg, local % seg))
    checks["every_loss_finite"] = bad_dispatches == 0
    # the warm-up dispatch, the window, the k=1 learn_k of the check
    checks["step_counter_closes"] = int(state.step) == chunk + steps + 1
    say("check notes " + repr(notes))
    batch = cfg.learner.batch_size
    say(f"window {window_s:.4f}s, {steps} grad steps, "
        f"{steps / window_s:.2f} steps/s, last loss {losses[-1]:.5f}")
    return {
        "attempted": steps,
        # a dispatch whose last loss is not finite fails all its steps
        "failed": bad_dispatches * chunk,
        "checks": checks,
        "end_to_end": {
            "learn_samples_per_s": steps * batch / window_s},
        "window_s": window_s, "grad_steps": steps, "batch_size": batch,
        "train_chunk": chunk, "chips": len(rt.devices),
        "fill": {"transitions": filled, "seconds": fill_s},
        "family": rt.cell.config["family"],
    }
