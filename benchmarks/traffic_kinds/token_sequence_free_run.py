"""Traffic kind `token_sequence_free_run`: `sequence_free_run`'s window
for the decoder family's token sequences. No actors, no server traffic,
no ingest: the replay is filled during set-up with seeded token
sequences generated on the device (benchmarks/harness/token_content.py)
through the program's own `learner.add`; the window dispatches
`learner.train_many(state, train_chunk)` back to back as
`ApexDriver._learner_loop_inner` does with obs off, a bounded number of
dispatches in flight so the closing fence is exact. The loop is
`learner_free_run.py`'s, written a third time because the fill, the
checks and the step's counters are this family's.

The learner and its state are the program's own (`ApexDriver(cfg)`:
family_setup, HBM fits-check, `SingleChipLearner` with the decoder_q
family); the driver is never `run()`. One chip: the share of an 8-way
expert-parallel layer runs without its exchange.

Parameters (benchmarks/traffic/<mix>.json): `ring_fill`,
`fill_sequences_per_add`, `token_zipf_exponent`,
`priority_lognormal_sigma`, `terminal_one_in`, `episode_tail_one_in`,
`reward_one_in`, `max_dispatches_in_flight`, `trace_window_s`.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ape_x_dqn_tpu.runtime.driver import ApexDriver
from benchmarks.harness import flops_glm_moe, token_sequence_checks
from benchmarks.harness import token_content as tc
from benchmarks.harness.device import say

COUNTERS = ("moe_rows", "moe_rows_grad", "moe_load_max_over_mean")


def _fill(rt, driver, state, content: tc.Content):
    """Fill `ring_fill` of the replay with seeded sequences, ring slot
    k holding global sequence k.
    -> (state, sequences written, fenced seconds)."""
    want = int(driver.capacity * float(rt.params["ring_fill"]))
    g = min(int(rt.params["fill_sequences_per_add"]), want)
    gen = jax.jit(lambda first: tc.sequences(
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
        raise ValueError("token_sequence_free_run drives the single-chip "
                         "learner; the cell's layout is dp=tp=1")
    # learner.mfu looks the family's FLOP count up by name and passes
    # `sizes` alone: bind the decoder's sizes here
    flops_glm_moe.register(rt.cell.config["model_sizes"])
    say("imports done; building ApexDriver")
    driver = ApexDriver(cfg)
    try:
        return _run(rt, cfg, driver)
    finally:
        driver.server.stop()   # the only thread the constructor starts


def _run(rt, cfg, driver) -> dict:
    learner, state = driver.learner, driver.state
    driver.state = None        # the one reference: train_many donates it
    content = tc.content(cfg, driver.spec, rt.seed, rt.params)
    chunk = max(min(cfg.learner.train_chunk, cfg.learner.publish_every), 1)
    say(f"learner {type(learner).__name__} ({learner.family.name}), "
        f"replay {driver.capacity} sequences of {cfg.replay.seq_length}, "
        f"batch {cfg.learner.batch_size}, train_chunk {chunk}")

    state, filled, fill_s = _fill(rt, driver, state, content)
    say(f"filled {filled} sequences in {fill_s:.2f}s")
    # warm the one graph the window uses
    state, m = learner.train_many(state, chunk)
    jax.block_until_ready(m["loss"])
    say("train_many warm")
    rt.setup_done()

    max_in_flight = int(rt.params["max_dispatches_in_flight"])
    seen = []
    annotate = jax.profiler.TraceAnnotation
    with rt.window():
        t0 = time.monotonic()
        deadline = t0 + rt.seconds
        while time.monotonic() < deadline:
            with annotate("bench.train_dispatch"):
                state, m = learner.train_many(state, chunk)
            seen.append({k: m[k] for k in ("loss",) + COUNTERS})
            if len(seen) > max_in_flight:
                with annotate("bench.wait_in_flight"):
                    seen[-1 - max_in_flight]["loss"].block_until_ready()
        with annotate("bench.closing_fence"):
            jax.block_until_ready(m["loss"])
        window_s = time.monotonic() - t0

    steps = len(seen) * chunk
    seen = jax.device_get(seen)
    losses = np.asarray([s["loss"] for s in seen])
    bad_dispatches = int((~np.isfinite(losses)).sum())
    peak_window = _peak_bytes(rt)
    state, checks, notes = token_sequence_checks.check_learner(
        learner, driver.net, state, cfg,
        # ring slot k holds global sequence k
        lambda idx: tc.sequences(np, content, idx))
    checks["every_loss_finite"] = bad_dispatches == 0
    # the warm-up dispatch, the window, the k=1 learn_k of the check
    checks["step_counter_closes"] = int(state.step) == chunk + steps + 1
    notes["peak_bytes_window_then_checks"] = [peak_window, _peak_bytes(rt)]
    say("check notes " + repr(notes))
    batch = cfg.learner.batch_size
    say(f"window {window_s:.4f}s, {steps} grad steps, "
        f"{steps / window_s:.3f} steps/s, last loss {losses[-1]:.5f}")
    return {
        "attempted": steps,
        # a dispatch whose last loss is not finite fails all its steps
        "failed": bad_dispatches * chunk,
        "checks": checks,
        # a sample is one replayed 512-token sequence
        "end_to_end": {
            "learn_samples_per_s": steps * batch / window_s},
        "window_s": window_s, "grad_steps": steps, "batch_size": batch,
        "train_chunk": chunk, "chips": len(rt.devices),
        # tokens stored, so the rate compares with the other rings'
        "fill": {"transitions": filled * cfg.replay.seq_length,
                 "seconds": fill_s},
        "family": rt.cell.config["family"],
        # each dispatch reports its last step's counters
        "moe": {
            "rows_per_step": float(np.mean(
                [s["moe_rows"] for s in seen])),
            "rows_grad_per_step": float(np.mean(
                [s["moe_rows_grad"] for s in seen])),
            "load_max_over_mean": float(np.mean(
                [s["moe_load_max_over_mean"] for s in seen]))},
    }


def _peak_bytes(rt) -> int | None:
    stats = rt.devices[0].memory_stats()
    return int(stats["peak_bytes_in_use"]) if stats else None
