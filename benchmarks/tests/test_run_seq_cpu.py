"""The traffic kind `sequence_free_run` end to end at a tiny size on the
CPU, through `runner.run_cell` (the function behind the command, minus
the device gate), as test_run_cpu.py does for the other two kinds."""

import dataclasses
import json
import time

import numpy as np
import pytest

from benchmarks.harness import cells, flops, runner

# widths cut for the CPU only here: 8-step sequences, LSTM 32, batch 4
TINY = ("learner.batch_size=4", "replay.capacity=64",
        "replay.seq_length=8", "replay.burn_in=3", "replay.seq_overlap=4",
        "learner.n_step=2", "network.lstm_size=32",
        "network.torso_dense=64")
TRAFFIC = {"fill_sequences_per_add": 16, "episode_tail_one_in": 4,
           "terminal_one_in": 16}


def _tiny_run(monkeypatch, patch_kind=None) -> tuple[dict, dict]:
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    cell = cells.resolve("r2d2_offline")
    cell = dataclasses.replace(cell, traffic={**cell.traffic, **TRAFFIC})
    facts = {}
    real = cells.traffic_kind

    def spying(c):
        kind = real(c)
        if patch_kind:
            patch_kind(kind)

        def run(rt):
            facts.update(kind.run(rt))
            return facts
        return type("SpiedKind", (), {"run": staticmethod(run)})

    monkeypatch.setattr(cells, "traffic_kind", spying)
    result = runner.run_cell(cell, seed=2147483900, seconds=1.0,
                             trace=False, t_process_start=time.monotonic(),
                             devices=jax.devices()[:1], cfg_overrides=TINY)
    return result, facts


def test_sequence_kind_tiny(monkeypatch):
    result, facts = _tiny_run(monkeypatch)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    cell = cells.resolve("r2d2_offline")
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end} \
        == {"learn_samples_per_s", "peak_hbm_gib", "setup_s"}
    json.dumps(result)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] == facts["grad_steps"] > 0
    assert facts["grad_steps"] % facts["train_chunk"] == 0
    assert set(facts["checks"]) == {
        "sequences_are_what_was_written",
        "q_loss_and_priorities_match_reference", "tree_root_is_leaf_sum",
        "valid_frac_is_the_seeded_share", "every_loss_finite",
        "step_counter_closes"}
    # what the readers that exist read
    assert facts["batch_size"] == 4 and facts["chips"] == 1
    assert facts["fill"]["transitions"] == 64 * 8     # steps stored
    assert facts["fill"]["seconds"] > 0
    # a sample is one replayed sequence
    assert result["metrics"]["learn_samples_per_s"]["value"] == \
        pytest.approx(facts["grad_steps"] * 4 / facts["window_s"])
    # learner.mfu finds the family's count, bound to the file's sizes
    assert flops.TRAIN_STEP_FLOPS[facts["family"]](
        facts["runtime"].sizes) == 351_550_832_640.0


def test_one_wrong_byte_in_a_sampled_sequence_turns_correct_false(
        monkeypatch):
    def patch(kind):
        real = kind.sc.sequences

        def sequences(xp, content, ids):
            out = real(xp, content, ids)
            if xp is np:    # the host's recomputation, not the fill
                out["seq_frames"][0, 3, 5, 7] ^= 1
            return out
        monkeypatch.setattr(kind.sc, "sequences", sequences)

    result, facts = _tiny_run(monkeypatch, patch)
    assert not result["correct"]
    wrong = [k for k, ok in facts["checks"].items() if not ok]
    assert wrong == ["sequences_are_what_was_written"]
