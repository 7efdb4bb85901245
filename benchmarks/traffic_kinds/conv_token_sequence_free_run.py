"""Traffic kind `conv_token_sequence_free_run`:
`decoder_token_sequence_free_run`'s window for a net of the decoder
family WITH CONV LAYERS AND A TIED HEAD (models/lfm2_moe_q.py: gated
short convolutions beside full attention, over experts; the head is the
embedding). It stands BESIDE the general kind and not inside it for the
reasons benchmarks/README_conv_cell.md gives: that kind's `FAMILIES`
table and its `COUNTERS` are inside a file only a `benchmark` PR may
edit, and its check makes a departure (`window_ignored`) of a window
this net does not have and walks a head and an embedding that are two
leaves. What is model-free is IMPORTED, none of it copied: the fill and
the peak reading are token_sequence_free_run.py's, the content
harness/token_content.py, the expert layer's counters the general
kind's `COUNTERS`.

The step's counters: the general kind's five, and the one
`runtime/family.decoder_q_family` reports for a net with conv layers -
`conv_positions` (positions that passed a conv operator in the online
net's forward pass; the reader `conv.positions_mixed` takes it from
`facts["conv"]["positions_mixed"]`).

`FAMILIES`: the configuration file's `family` -> (reference, mapper,
FLOP module, the departures its check must refuse), as the general
kind's. A further decoder with conv layers is a row here and its three
files. Parameters (benchmarks/traffic/<mix>.json): the general kind's.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from ape_x_dqn_tpu.runtime.driver import ApexDriver
from benchmarks.harness import (conv_sequence_checks, flops_lfm2,
                                lfm2_params)
from benchmarks.harness import token_content as tc
from benchmarks.harness.device import say
from benchmarks.reference import lfm2_moe_q
from benchmarks.traffic_kinds import decoder_token_sequence_free_run as general
from benchmarks.traffic_kinds import token_sequence_free_run as glm_kind

COUNTERS = general.COUNTERS + ("conv_positions",)

FAMILIES = {
    flops_lfm2.FAMILY: (
        lfm2_moe_q, lfm2_params, flops_lfm2,
        {name: {name: True} for name in (
            "conv_tail_ignored", "conv_out_gate_left_out",
            "conv_silu_added", "qk_norm_left_out", "head_untied")}),
}


def run(rt) -> dict:
    cfg = rt.run_config()
    if cfg.parallel.dp * cfg.parallel.tp != 1:
        raise ValueError("conv_token_sequence_free_run drives the "
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
    state, checks, notes = conv_sequence_checks.check_learner(
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
    mean = lambda key: float(np.mean([s[key] for s in seen]))  # noqa: E731
    say("check notes " + repr(notes))
    batch = cfg.learner.batch_size
    say(f"window {window_s:.4f}s, {steps} grad steps, "
        f"{steps / window_s:.3f} steps/s, last loss {losses[-1]:.5f}; "
        f"conv_positions {mean('conv_positions'):.1f}")
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
        "moe": {"rows_per_step": mean("moe_rows"),
                "rows_grad_per_step": mean("moe_rows_grad"),
                "load_max_over_mean": mean("moe_load_max_over_mean"),
                "compact_share": mean("moe_compact_share")},
        "conv": {"positions_mixed": mean("conv_positions")},
    }
