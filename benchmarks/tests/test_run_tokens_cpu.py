"""The traffic kind `token_sequence_free_run` end to end at a tiny size
on the CPU, through `runner.run_cell` (the function behind the command,
minus the device gate), as test_run_seq_cpu.py does for R2D2's kind."""

import dataclasses
import json
import time

import numpy as np
import pytest

from benchmarks.harness import cells, flops, runner

# widths cut for the CPU only here: hidden 64, 3 layers, 8 experts of
# which 4 are held, 32 of 64 vocabulary rows, 16-token sequences
TINY = ("network.glm.hidden_size=64", "network.glm.intermediate_size=128",
        "network.glm.moe_intermediate_size=32",
        "network.glm.num_hidden_layers=3",
        "network.glm.num_attention_heads=2", "network.glm.q_lora_rank=24",
        "network.glm.kv_lora_rank=16", "network.glm.qk_nope_head_dim=12",
        "network.glm.qk_rope_head_dim=8", "network.glm.v_head_dim=16",
        "network.glm.n_routed_experts=8",
        "network.glm.num_experts_per_tok=2", "network.glm.vocab_size=64",
        "network.glm.shard_count=2", "env.num_tokens=32",
        "learner.batch_size=4", "replay.capacity=64",
        "replay.seq_length=16", "replay.burn_in=4", "replay.seq_overlap=8",
        "learner.n_step=2")
TRAFFIC = {"fill_sequences_per_add": 16, "episode_tail_one_in": 4,
           "terminal_one_in": 16, "reward_one_in": 4}
CELL = "glm47_flash_offline"


def _tiny_run(monkeypatch, patch_kind=None) -> tuple[dict, dict]:
    import jax

    from benchmarks.harness import token_sequence_checks as checks

    jax.config.update("jax_enable_compilation_cache", False)
    # at these widths a norm gain is a leaf of 16 values and a step
    # trains 48 tokens: ratios of two such norms swing (read here over
    # five runs: worst leaf 1.2-2.1, median leaf 0.94-1.12, where the
    # cell's leaves start at 512 values); a wrong backward pass reads
    # 60 and 6.8 (the last test)
    monkeypatch.setattr(checks, "GRAD_RATIO", 6.0)
    monkeypatch.setattr(checks, "GRAD_MEDIAN_RATIO", 2.0)
    cell = cells.resolve(CELL)
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


def test_token_kind_tiny(monkeypatch):
    result, facts = _tiny_run(monkeypatch)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    cell = cells.resolve(CELL)
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end} \
        == {"learn_samples_per_s", "peak_hbm_gib", "setup_s"}
    json.dumps(result)
    assert result["correct"] and result["failed"] == 0, facts["checks"]
    assert result["attempted"] == facts["grad_steps"] > 0
    assert facts["grad_steps"] % facts["train_chunk"] == 0
    assert set(facts["checks"]) == {
        "sequences_are_what_was_written",
        "q_loss_and_priorities_match_reference",
        "gradients_match_reference",
        "routing_matches_reference_outside_margin",
        "moe_rows_counter_matches_selection", "tree_root_is_leaf_sum",
        "valid_frac_is_the_seeded_share", "every_loss_finite",
        "step_counter_closes"}
    # what the readers that exist read
    assert facts["batch_size"] == 4 and facts["chips"] == 1
    assert facts["train_chunk"] == 2
    assert facts["fill"]["transitions"] == 64 * 16     # tokens stored
    assert facts["fill"]["seconds"] > 0
    # a sample is one replayed sequence
    assert result["metrics"]["learn_samples_per_s"]["value"] == \
        pytest.approx(facts["grad_steps"] * 4 / facts["window_s"])
    # the step's own counters reach the readers
    moe = facts["moe"]
    assert 0 < moe["rows_grad_per_step"] < moe["rows_per_step"]
    assert moe["load_max_over_mean"] >= 1.0
    # learner.mfu finds the family's count, bound to the file's sizes
    assert flops.TRAIN_STEP_FLOPS[facts["family"]](
        facts["runtime"].sizes) == pytest.approx(16.2305e12, rel=1e-4)


def test_one_wrong_id_in_a_sampled_sequence_turns_correct_false(
        monkeypatch):
    def patch(kind):
        real = kind.tc.sequences

        def sequences(xp, content, ids):
            out = real(xp, content, ids)
            if xp is np:    # the host's recomputation, not the fill
                out["obs"][0, 3] ^= 1
            return out
        monkeypatch.setattr(kind.tc, "sequences", sequences)

    result, facts = _tiny_run(monkeypatch, patch)
    assert not result["correct"]
    wrong = [k for k, ok in facts["checks"].items() if not ok]
    assert wrong == ["sequences_are_what_was_written"]


def test_a_wrong_backward_pass_turns_correct_false(monkeypatch):
    """The combine's cotangent halved: every loss stays finite, the
    forward pass is untouched (Q and routing hold), and the gradient
    comparison says so."""
    from ape_x_dqn_tpu.models import glm_moe_q

    monkeypatch.setattr(
        glm_moe_q._combine, "bwd",
        lambda n, order, g: (0.5 * g[order // (order.shape[0] // n)],
                             None, None))
    result, facts = _tiny_run(monkeypatch)
    assert not result["correct"] and result["failed"] == 0
    checks = facts["checks"]
    assert not checks["gradients_match_reference"]
    assert checks["routing_matches_reference_outside_margin"]
    assert checks["every_loss_finite"]
