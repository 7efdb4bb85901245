"""Traffic kind `looped_token_sequence_free_run`:
`decoder_token_sequence_free_run`'s window for a net of the decoder
family WITHOUT an expert layer whose blocks are applied several times a
forward pass (models/ouro_q.py). That kind cannot take such a net
without an edit (its `FAMILIES` table is inside the file, its
`facts["moe"]` block and its counters are the expert layer's, and its
check stacks per-layer selections), so this one stands beside it: the
same table of (reference, mapper, FLOP module, the departures its check
must refuse) by the configuration file's `family`, handed to
harness/looped_sequence_checks.py; the fill and the peak reading are
token_sequence_free_run.py's, imported; the window is that kind's loop
- `train_many` dispatched back to back, a bounded number in flight,
a closing fence - over THIS family's counters. A further looped decoder
is a row here and its three files
(benchmarks/README_looped_cell.md); folding the kinds into one is a
`benchmark` PR's (ROADMAP D9).

The learner and its state are the program's own (`ApexDriver(cfg)`:
family_setup, HBM fits-check, `SingleChipLearner` with the decoder_q
family); the driver is never `run()`. The graphs a run compiles are the
three it needs: `train_many` before the window, the check's `learn_k`
and its gradient program after it (plus the reference's pieces).

The step's counters: `loop_block_applications` (blocks applied per net
forward: loop steps x layers) and `loop_exit_mass_last` (the exit
distribution's mass on the last loop step, mean over trained tokens),
handed on as `facts["loop"]`; the reader `loop.block_applications`
takes the first.

Parameters (benchmarks/traffic/<mix>.json): token_sequence_free_run's,
and `show_limits` (absent: false): the check also computes the readings
that have to fail (harness/looped_sequence_checks.py).
"""

from __future__ import annotations

import time

import jax
import numpy as np

from ape_x_dqn_tpu.runtime.driver import ApexDriver
from benchmarks.harness import flops_ouro, looped_sequence_checks, ouro_params
from benchmarks.harness import token_content as tc
from benchmarks.harness.device import say
from benchmarks.reference import ouro_q
from benchmarks.traffic_kinds import token_sequence_free_run as glm_kind

COUNTERS = ("loop_block_applications", "loop_exit_mass_last")

# the configuration file's `family` -> (reference, mapper, the module
# that registers its FLOP count, {departure: fields of the reference's
# Sizes}: readings its check must refuse under `show_limits`)
FAMILIES = {
    flops_ouro.FAMILY: (
        ouro_q, ouro_params, flops_ouro,
        {"three_loop_steps_for_four": {"loop_steps": 3},
         "every_step_reads_step_0_prefix": {"prefix_from": "step_0"},
         "final_norm_once_after_the_loop": {"final_norm": "after_loop"},
         "no_post_sublayer_norms": {"post_norms": False}}),
}


def run(rt) -> dict:
    cfg = rt.run_config()
    if cfg.parallel.dp * cfg.parallel.tp != 1:
        raise ValueError("looped_token_sequence_free_run drives the "
                         "single-chip learner; the cell's layout is dp=tp=1")
    # learner.mfu looks the family's FLOP count up by name and passes
    # `sizes` alone: bind the decoder's sizes here
    FAMILIES[rt.cell.config["family"]][2].register(
        rt.cell.config["model_sizes"])
    say("imports done; building ApexDriver")
    driver = ApexDriver(cfg)
    try:
        return _run(rt, cfg, driver)
    finally:
        driver.server.stop()   # the only thread the constructor starts


def _run(rt, cfg, driver) -> dict:
    ref, mapper, _, departures = FAMILIES[rt.cell.config["family"]]
    learner, state = driver.learner, driver.state
    driver.state = None        # the one reference: train_many donates it
    content = tc.content(cfg, driver.spec, rt.seed, rt.params)
    chunk = max(min(cfg.learner.train_chunk, cfg.learner.publish_every), 1)
    say(f"learner {type(learner).__name__} ({learner.family.name}), "
        f"replay {driver.capacity} sequences of {cfg.replay.seq_length}, "
        f"batch {cfg.learner.batch_size}, train_chunk {chunk}")

    state, filled, fill_s = glm_kind._fill(rt, driver, state, content)
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
    peak_window = glm_kind._peak_bytes(rt)
    state, checks, notes = looped_sequence_checks.check_learner(
        (ref, mapper), learner, driver.net, state, cfg,
        # ring slot k holds global sequence k
        lambda idx: tc.sequences(np, content, idx),
        departures=departures,
        show_limits=bool(rt.params.get("show_limits", False)),
        note=lambda what: say(f"check: {what} ({rt.watcher.snapshot()[1]:.0f}"
                              "s of compiling so far)"))
    checks["every_loss_finite"] = bad_dispatches == 0
    # the warm-up dispatch, the window, the k=1 learn_k of the check
    checks["step_counter_closes"] = int(state.step) == chunk + steps + 1
    notes["peak_bytes_window_then_checks"] = [peak_window,
                                              glm_kind._peak_bytes(rt)]
    say("check notes " + repr(notes))
    batch = cfg.learner.batch_size
    say(f"window {window_s:.4f}s, {steps} grad steps, "
        f"{steps / window_s:.3f} steps/s, last loss {losses[-1]:.5f}")
    mean = lambda key: float(np.mean([s[key] for s in seen]))  # noqa: E731
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
        # tokens stored, so the rate compares with the other rings'
        "fill": {"transitions": filled * cfg.replay.seq_length,
                 "seconds": fill_s},
        "family": rt.cell.config["family"],
        # each dispatch reports its last step's counters
        "loop": {"block_applications": mean("loop_block_applications"),
                 "exit_mass_last": mean("loop_exit_mass_last")},
    }
