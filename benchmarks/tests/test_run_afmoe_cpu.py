"""The traffic kind `afmoe_token_sequence_free_run` end to end at a tiny
size on the CPU, through `runner.run_cell` (the function behind the
command, minus the device gate), as test_run_tokens_cpu.py does for
GLM's kind."""

import dataclasses
import json
import time

import pytest

from benchmarks.harness import cells, flops, runner

# widths cut for the CPU only here: hidden 64, 4 / 2 heads of 16, 3
# layers (sliding, sliding, full), 8 experts of which 4 are held, 32 of
# 64 vocabulary rows, a window of 8 inside 32-token sequences
TINY = ("network.afmoe.hidden_size=64", "network.afmoe.intermediate_size=128",
        "network.afmoe.moe_intermediate_size=32",
        "network.afmoe.num_hidden_layers=3",
        "network.afmoe.layer_types=('sliding_attention',"
        "'sliding_attention','full_attention')",
        "network.afmoe.num_attention_heads=4",
        "network.afmoe.num_key_value_heads=2", "network.afmoe.head_dim=16",
        "network.afmoe.sliding_window=8",
        # the share first: each override is checked as it is set
        "network.afmoe.shard_count=2", "network.afmoe.vocab_shard_count=2",
        "network.afmoe.num_experts=8", "network.afmoe.num_experts_per_tok=2",
        "network.afmoe.vocab_size=64", "env.num_tokens=32",
        "learner.batch_size=4", "replay.capacity=64",
        "replay.seq_length=32", "replay.burn_in=12",
        "replay.seq_overlap=16", "learner.n_step=2")
TRAFFIC = {"fill_sequences_per_add": 16, "episode_tail_one_in": 4,
           "terminal_one_in": 16, "reward_one_in": 4}
CELL = "trinity_mini_offline"


class _Clock:
    """`time` for the kind's window loop: a tenth of a second a call,
    so a window of one second is nine dispatches on any machine, however
    loaded - the parameters the check then reads, and with them what it
    says at these widths, depend on how many steps were taken."""

    def __init__(self):
        self.now = 0.0

    def monotonic(self) -> float:
        self.now += 0.1
        return self.now


def _tiny_run(monkeypatch, patch_kind=None, **mix) -> tuple[dict, dict]:
    import jax

    from benchmarks.harness import token_sequence_checks as limits

    jax.config.update("jax_enable_compilation_cache", False)
    # as test_run_tokens_cpu.py: at these widths a norm gain is a leaf
    # of 16 values and ratios of two such norms swing; a wrong backward
    # pass reads far beyond (the last test)
    monkeypatch.setattr(limits, "GRAD_RATIO", 6.0)
    monkeypatch.setattr(limits, "GRAD_MEDIAN_RATIO", 2.0)
    cell = cells.resolve(CELL)
    cell = dataclasses.replace(cell,
                               traffic={**cell.traffic, **TRAFFIC, **mix})
    facts = {}
    real = cells.traffic_kind

    def spying(c):
        kind = real(c)
        monkeypatch.setattr(kind, "time", _Clock())
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


def test_afmoe_kind_tiny(monkeypatch, capsys):
    # with the two readings that have to fail, which a run of the cell
    # leaves out (the next test runs as the cell does)
    result, facts = _tiny_run(monkeypatch, show_limits=True)
    said = capsys.readouterr().err
    assert "'window_ignored': {'passes': False" in said
    assert "'one_bit_less': {'passes': " in said
    assert "'grad_one_bit_less': {" in said
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    cell = cells.resolve(CELL)
    assert cell.chips == 1
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
    assert facts["fill"]["transitions"] == 64 * 32     # tokens stored
    assert facts["fill"]["seconds"] > 0
    assert result["metrics"]["learn_samples_per_s"]["value"] == \
        pytest.approx(facts["grad_steps"] * 4 / facts["window_s"])
    moe = facts["moe"]
    assert 0 < moe["rows_grad_per_step"] < moe["rows_per_step"]
    assert moe["load_max_over_mean"] >= 1.0
    # learner.mfu finds the family's count, bound to the file's sizes
    assert facts["family"] == "afmoe_swa_q"
    assert flops.TRAIN_STEP_FLOPS[facts["family"]](
        facts["runtime"].sizes) == pytest.approx(41.698e12, rel=1e-4)


def test_a_wrong_attention_backward_turns_correct_false(monkeypatch, capsys):
    """The attention's key cotangent halved: every loss stays finite,
    the forward pass is untouched (Q and routing hold), and the
    gradient comparison says so."""
    from ape_x_dqn_tpu.ops import blockwise_attention as ba

    real = ba._attend_bwd

    def halved(geo, res, d_out):
        dq, dk, dv = real(geo, res, d_out)
        return dq, 0.5 * dk, dv

    monkeypatch.setattr(ba._attend, "bwd", halved)
    result, facts = _tiny_run(monkeypatch)
    said = capsys.readouterr().err
    assert "check notes" in said and "one_bit_less" not in said
    assert "window_ignored" not in said
    assert not result["correct"] and result["failed"] == 0
    checks = facts["checks"]
    assert not checks["gradients_match_reference"]
    # Q and the loss hold. (Not the priorities' rule: the window trains
    # with the wrong gradient for as many steps as a second holds, and
    # a priority is the largest |TD| of a sequence - one double-Q flip
    # between near-tied ids moves it, in some runs, past its limit.)
    notes = said[said.index("check notes"):]
    assert "'ok': {'q': True, 'priorities': " in notes, notes
    assert "'loss': True}" in notes, notes
    assert checks["every_loss_finite"]


def test_q_rule_in_float32_is_matches_reference():
    """`held_to_reference` decides Q and the loss as
    token_sequence_checks.matches_reference does, to float32's last
    digits, on either side of the limit."""
    import numpy as np

    from benchmarks.harness import afmoe_sequence_checks as checks
    from benchmarks.harness import token_sequence_checks as glm

    rng = np.random.default_rng(5)
    shape = (2, 24, 64)
    want_q = rng.standard_normal(shape).astype(np.float32)
    td = rng.standard_normal(shape[:2]).astype(np.float32)
    want = {"q": want_q, "td": td, "valid": np.ones(shape[:2], np.float32),
            "priorities": np.abs(td).max(axis=1).astype(np.float64),
            "loss": 0.3}
    noisy = lambda x, by: (x + by * rng.standard_normal(      # noqa: E731
        x.shape)).astype(x.dtype)
    stated = {"q": noisy(want_q, 1e-2), "td": noisy(td, 1e-2),
              "priorities": noisy(want["priorities"], 1e-2)}
    compare = np.ones(2, bool)
    for by, passes in ((1e-2, True), (2e-2, False)):
        got = {"q": noisy(want_q, by), "loss": 0.3 + by * 1e-2,
               "priorities": want["priorities"] + 1e-3}
        ok, notes = checks.held_to_reference(got, want, stated, compare, 0.9)
        _, theirs = glm.matches_reference(got, want, stated, compare, 0.9)
        assert notes["ok"]["q"] == theirs["ok"]["q"] == passes
        assert notes["ok"]["loss"] == theirs["ok"]["loss"]
        for k in ("q_err_q95", "q_unit", "loss_unit", "loss_err"):
            assert notes[k] == pytest.approx(theirs[k], rel=1e-5)
        assert ok == passes
    got["q"][0, 0, 0] = np.nan
    assert not checks.held_to_reference(got, want, stated, compare, 0.9)[0]


def test_the_reference_loss_is_a_mean_over_sequences():
    """What `reference_on` relies on when it takes the loss one
    sequence at a time."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import afmoe_q as ref

    rng = np.random.default_rng(9)
    b, t, a = 3, 20, 16
    q, q_t = (rng.standard_normal((b, t, a)).astype(np.float32)
              for _ in range(2))
    actions = rng.integers(0, a, (b, t))
    rewards = rng.standard_normal((b, t)).astype(np.float32)
    terminals = rng.random((b, t)) < 0.1
    mask = (np.arange(t)[None, :] < np.array([20, 13, 17])[:, None])
    weights = rng.random(b).astype(np.float32)
    kw = dict(n_step=2, gamma=0.99, eta=0.9)
    whole, aux = ref.td_loss(q, q_t, actions, rewards, terminals,
                             mask.astype(np.float32), weights, **kw)
    singles = [ref.td_loss(q[i:i + 1], q_t[i:i + 1], actions[i:i + 1],
                           rewards[i:i + 1], terminals[i:i + 1],
                           mask[i:i + 1].astype(np.float32),
                           weights[i:i + 1], **kw) for i in range(b)]
    assert float(whole) == pytest.approx(
        float(jnp.mean(jnp.stack([s[0] for s in singles]))), rel=1e-6)
    for k in ("priorities", "td", "valid"):
        np.testing.assert_allclose(
            np.concatenate([np.asarray(s[1][k]) for s in singles]),
            np.asarray(aux[k]), rtol=1e-6)
