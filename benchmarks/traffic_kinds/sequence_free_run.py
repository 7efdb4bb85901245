"""Traffic kind `sequence_free_run`: `learner_free_run`'s window for a
sequence replay (R2D2). No actors, no server traffic, no ingest: the
replay is filled during set-up with seeded sequences generated on the
device (benchmarks/harness/sequence_content.py) through the program's
own `learner.add`; the window dispatches `learner.train_many(state,
train_chunk)` back to back as `ApexDriver._learner_loop_inner` does
with obs off, a bounded number of dispatches in flight so the closing
fence is exact. The loop is `learner_free_run.py`'s; it is written
again because that file's fill and checks are the frame ring's.

The learner and its state are the program's own (`ApexDriver(cfg)`:
family_setup, HBM fits-check, `SequenceLearner` on one chip); the
driver is never `run()`. One chip only: the dp form of the sequence
learner has no cell yet.

Parameters (benchmarks/traffic/<mix>.json): `ring_fill`,
`fill_sequences_per_add`, `priority_lognormal_sigma`, `terminal_one_in`,
`episode_tail_one_in`, `init_state_scale`, `max_dispatches_in_flight`,
`trace_window_s`.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ape_x_dqn_tpu.runtime.driver import ApexDriver
from benchmarks.harness import flops_r2d2, sequence_checks
from benchmarks.harness import sequence_content as sc
from benchmarks.harness.device import say


def _fill(rt, driver, state, content: sc.Content):
    """Fill `ring_fill` of the replay with seeded sequences, ring slot
    k holding global sequence k.
    -> (state, sequences written, fenced seconds)."""
    want = int(driver.capacity * float(rt.params["ring_fill"]))
    g = min(int(rt.params["fill_sequences_per_add"]), want)
    gen = jax.jit(lambda first: sc.sequences(
        jnp, content, first + jnp.arange(g, dtype=jnp.int32)))
    t0 = time.monotonic()
    for c in range(want // g):
        items = gen(jnp.int32(c * g))
        pri = items.pop("priorities")
        state = driver.learner.add(state, items, pri)
    jax.block_until_ready(state.replay.size)
    return state, (want // g) * g, time.monotonic() - t0


def run(rt) -> dict:
    cfg = rt.run_config()
    if cfg.parallel.dp * cfg.parallel.tp != 1:
        raise ValueError("sequence_free_run drives the single-chip "
                         "SequenceLearner; the cell's layout is dp=tp=1")
    # learner.mfu looks the family's FLOP count up by name and passes
    # `sizes` alone: bind the recurrent sizes here
    flops_r2d2.register(rt.cell.config["sequence_sizes"])
    say("imports done; building ApexDriver")
    driver = ApexDriver(cfg)
    try:
        return _run(rt, cfg, driver)
    finally:
        driver.server.stop()   # the only thread the constructor starts


def _run(rt, cfg, driver) -> dict:
    learner, state = driver.learner, driver.state
    content = sc.content(cfg, driver.spec, rt.seed, rt.params)
    chunk = max(min(cfg.learner.train_chunk, cfg.learner.publish_every), 1)
    say(f"learner {type(learner).__name__}, replay {driver.capacity} "
        f"sequences of {cfg.replay.seq_length}, batch "
        f"{cfg.learner.batch_size}, train_chunk {chunk}")

    state, filled, fill_s = _fill(rt, driver, state, content)
    say(f"filled {filled} sequences in {fill_s:.2f}s")
    # warm the one graph the window uses
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
    state, checks, notes = sequence_checks.check_learner(
        learner, state, cfg, rt.sizes["cnn_strides"],
        # ring slot k holds global sequence k
        lambda idx: sc.sequences(np, content, idx))
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
        # a sample is one replayed sequence
        "end_to_end": {
            "learn_samples_per_s": steps * batch / window_s},
        "window_s": window_s, "grad_steps": steps, "batch_size": batch,
        "train_chunk": chunk, "chips": len(rt.devices),
        # steps stored, so the rate compares with the frame ring's
        "fill": {"transitions": filled * cfg.replay.seq_length,
                 "seconds": fill_s},
        "family": rt.cell.config["family"],
    }
