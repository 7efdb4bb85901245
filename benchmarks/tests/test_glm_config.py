"""configs/glm47_flash_ep8_1chip.json against the preset and against
the catalog row it was drawn from, the family's FLOP counts against a
hand count, the seeded token content under numpy and jax.numpy, the
decoder's readers on recorded traces, and the routing check."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import (cells, flops, flops_glm_moe, glm_scopes,
                                token_sequence_checks)
from benchmarks.harness import token_content as tc

CELL = "glm47_flash_offline"
CONF = cells.resolve(CELL).config
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
REDUCED = {"num_hidden_layers": (47, 5), "n_routed_experts": (64, 8),
           "vocab_size": (154_880, 19_360)}


def _cfg():
    from ape_x_dqn_tpu.configs import get_config
    from ape_x_dqn_tpu.runtime.train import apply_overrides

    return apply_overrides(get_config(CONF["preset"]), CONF["overrides"])


def test_model_sizes_are_what_preset_plus_overrides_build():
    cfg = _cfg()
    glm, m = cfg.network.glm, CONF["model_sizes"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_hidden_layers", "first_k_dense_replace",
                "num_attention_heads", "q_lora_rank", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "n_routed_experts", "n_shared_experts",
                "num_experts_per_tok", "norm_topk_prob",
                "routed_scaling_factor", "vocab_size", "rms_norm_eps",
                "rope_theta", "shard_count", "shard_index",
                "force_balanced_routing"):
        assert m[key] == getattr(glm, key), key
    from ape_x_dqn_tpu.models import build_network

    net = build_network(cfg.network, None)
    assert m["experts_held"] == net.experts_held == 8
    assert m["vocab_held"] == net.num_actions == CONF["sizes"]["num_actions"]
    assert m["parameters"] == net.param_count()
    for key, value in (
            ("seq_length", cfg.replay.seq_length),
            ("burn_in", cfg.replay.burn_in),
            ("seq_overlap", cfg.replay.seq_overlap),
            ("priority_eta", cfg.replay.priority_eta),
            ("value_rescale", cfg.learner.value_rescale),
            ("target_sync_every", cfg.learner.target_sync_every),
            ("lr", cfg.learner.lr), ("adam_eps", cfg.learner.adam_eps),
            ("max_grad_norm", cfg.learner.max_grad_norm)):
        assert m[key] == value, key
    assert cfg.network.kind == "glm_moe_q" and cfg.replay.kind == "sequence"
    assert CONF["family"] == flops_glm_moe.FAMILY
    assert CONF["layout"]["layer_shared_by"] == glm.shard_count == 8
    assert cfg.env.num_tokens == net.num_actions


def test_the_preset_is_the_published_model():
    from ape_x_dqn_tpu.configs import get_config

    glm = get_config(CONF["preset"]).network.glm
    assert (glm.num_hidden_layers, glm.n_routed_experts, glm.vocab_size,
            glm.shard_count) == (47, 64, 154_880, 1)
    assert CONF["model_sizes"]["num_hidden_layers_published"] == 47


def test_the_file_holds_the_catalog_rows_keys():
    """Every key of the catalog row's `config`, under the same name, at
    the same value — but the three `reduced` names, which give what is
    held here."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "GLM-4.7-Flash")
    assert CONF["source"].startswith(row["source_url"])
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == CONF["name"])
    assert entry["source"] == row["source_url"]
    for key, published in row["config"].items():
        if key in REDUCED:
            assert (published, CONF[key]) == REDUCED[key], key
            assert key in CONF["reduced"]
        else:
            assert CONF[key] == published, key
    # and the widths the program runs are the row's
    m = CONF["model_sizes"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "q_lora_rank", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "num_experts_per_tok", "n_shared_experts",
                "routed_scaling_factor", "first_k_dense_replace",
                "rms_norm_eps", "rope_theta", "n_routed_experts",
                "vocab_size"):
        assert m[key] == row["config"][key], key


def test_overrides_are_the_reduced_keys():
    keys = [o.split("=")[0] for o in CONF["overrides"]]
    assert keys == ["network.glm.num_hidden_layers",
                    "network.glm.shard_count", "env.num_tokens",
                    "actors.num_actors", "eval_every_steps",
                    "eval_episodes", "network.glm.force_balanced_routing"]
    assert CONF["reduced"][3:] == keys[3:6] + ["total_env_frames"]


def test_the_forced_selection_is_the_cells_alone_and_is_stated():
    """No cut of size but an assumption about the weights: off in the
    preset (the model's own top-4 of s + b), on in the cell, said under
    `assumed.routing`; the mix and the learner settings stay ISSUE
    30's."""
    from ape_x_dqn_tpu.configs import get_config

    assert not get_config(CONF["preset"]).network.glm.force_balanced_routing
    assert _cfg().network.glm.force_balanced_routing
    assert "force_balanced_routing" in CONF["assumed"]["routing"]
    assert CONF["model_sizes"]["lr"] == 1e-4
    assert set(cells.resolve(CELL).traffic) - {"kind", "why"} == {
        "ring_fill", "fill_sequences_per_add", "token_zipf_exponent",
        "priority_lognormal_sigma", "terminal_one_in",
        "episode_tail_one_in", "reward_one_in", "max_dispatches_in_flight",
        "trace_window_s"}


def test_flops_against_a_hand_count_at_the_published_widths():
    """By hand, per token and layer, MACs: q_a 2048 x 768 + q_b 768 x
    5120 + kv_a 2048 x 576 + kv_b 512 x 8960 + o 5120 x 2048 =
    21,757,952; scores and values 20 x (256 + 256) = 10,240 a key.
    Dense FFN 3 x 2048 x 10240 = 62,914,560. Expert layer: router 2048
    x 64, one shared and 4 x 8 / 64 = 0.5 routed experts of 3 x 2048 x
    1536 = 9,437,184. Head 2048 x 19360."""
    m = CONF["model_sizes"]
    mla = 21_757_952
    expert = 9_437_184
    def forward(keys):
        return 2.0 * (5 * (mla + 10_240 * keys) + 62_914_560
                      + 4 * (2048 * 64 + 1.5 * expert) + 2048 * 19_360)
    assert flops_glm_moe.token_forward_flops(m, 100.0) == forward(100.0)
    want = 16 * (2 * 128 * forward(64.5) + 4 * 384 * forward(320.5))
    flops_glm_moe.register(m)
    got = flops.TRAIN_STEP_FLOPS[CONF["family"]](CONF["sizes"])
    assert got == pytest.approx(want, rel=1e-12)
    assert got == pytest.approx(16.2305e12, rel=1e-4)
    # executed by the grouped matmuls: every row a forward, the online
    # net's trained rows three forwards' worth more
    assert flops_glm_moe.executed_expert_flops(1000, 100, m) == (
        6 * 2048 * 1536 * (1000 + 300))


def test_token_content_is_the_same_under_numpy_and_jax():
    cfg = _cfg()

    class Spec:
        num_actions = 19_360

    content = tc.content(cfg, Spec, 2**31 + 977, cells.resolve(CELL).traffic)
    ids = np.asarray([0, 1, 17, 65_535, 40_000], np.int32)
    host = tc.sequences(np, content, ids)
    dev = tc.sequences(jnp, content, jnp.asarray(ids))
    for k in tc.ITEM_KEYS:
        assert host[k].dtype == np.asarray(dev[k]).dtype
        np.testing.assert_array_equal(host[k], np.asarray(dev[k]))
    assert host["obs"].shape == (5, 512) and host["obs"].dtype == np.int32
    # the action at step t is the token at step t + 1
    np.testing.assert_array_equal(host["actions"][0, :-1],
                                  host["obs"][0, 1:])


def test_token_content_has_the_shapes_of_real_traffic():
    cfg = _cfg()

    class Spec:
        num_actions = 19_360

    content = tc.content(cfg, Spec, 5, cells.resolve(CELL).traffic)
    items = tc.sequences(np, content, np.arange(2048, dtype=np.int32))
    n_valid = items["mask"].sum(axis=1)
    tails = n_valid < 512
    assert 0.03 < tails.mean() < 0.10                  # one in 16
    assert n_valid[tails].min() >= 129 and n_valid[tails].max() <= 511
    last = (n_valid[tails] - 1).astype(int)
    assert (items["terminals"][tails, last] == 1).all()
    assert (items["obs"][items["mask"] == 0] == 0).all()
    assert (items["rewards"][items["terminals"] == 1] != 0).all()
    paid = (items["rewards"] != 0)[items["mask"] == 1].mean()
    assert 1 / 80 < paid < 1 / 50                      # one in 64 + terminals
    # Zipf: a few ids carry much of the traffic, most ids appear
    counts = np.bincount(items["obs"][items["mask"] == 1], minlength=19_360)
    assert 0 <= items["obs"].min() and items["obs"].max() < 19_360
    assert np.sort(counts)[-16:].sum() > 0.2 * counts.sum()
    assert (counts > 0).mean() > 0.9


def test_routing_check_minds_only_decided_selections():
    sys_topk = np.asarray([[[[0, 1]], [[2, 3]]]])       # [1, 2, 1, 2]
    own = np.asarray([[[[1, 0]], [[2, 5]]]])
    near = np.asarray([[[0.5], [0.001]]], np.float32)
    ok, notes = token_sequence_checks.routing_agrees(sys_topk, own, near)
    assert ok and notes["selections_differing_share"] == 0.5
    assert notes["inside_margin_share"] == 0.5
    far = np.asarray([[[0.5], [0.2]]], np.float32)
    ok, notes = token_sequence_checks.routing_agrees(sys_topk, own, far)
    assert not ok
    assert notes["largest_gap_of_a_differing_selection"] == pytest.approx(0.2)


def _facts(path, **more):
    class Rt:
        cell = cells.resolve(CELL)
        devices = [type("D", (), {"device_kind": "TPU v5 lite"})]

        @staticmethod
        def newest_xplane():
            return path

    return {"runtime": Rt, "train_chunk": 2, "trace": {"devices": [{
        "busy_ns": 1_000_000,
        "modules": {"jit_train_many(1)": {"median_ns": 500_000_000}}}]},
        **more}


def test_readers_find_nothing_in_a_program_without_the_scopes():
    """What the parent commit gives: ops with name stacks but no `glm.`
    scope, and no counters. Every new reader returns nothing."""
    facts = _facts(os.path.join(DATA, "scope_probe.xplane.pb"))
    for name in ("learner.moe_share", "learner.mla_share",
                 "moe.load_max_over_mean",
                 "kernels.moe_expert_mm_roofline"):
        assert cells.layer_metric_reader(name).read(facts) is None, name
    assert set(facts["glm_scope_ns"]) == set(glm_scopes.SCOPES)
    assert not any(facts["glm_scope_ns"].values())


def test_readers_on_a_recorded_trace(monkeypatch):
    """scope_probe.xplane.pb's two scopes stand in for the decoder's
    (the walk is scope_stats.py's, held by its own test): the shares
    are self time over busy time, and the roofline share is executed
    FLOP over the scope's time per step over the peak."""
    probe = os.path.join(DATA, "scope_probe.xplane.pb")
    monkeypatch.setattr(glm_scopes, "SCOPES",
                        ("r2d2.torso", "r2d2.lstm_scan"))
    facts = _facts(probe, moe={"rows_per_step": 30_000.0,
                               "rows_grad_per_step": 12_000.0,
                               "load_max_over_mean": 2.5})
    assert cells.layer_metric_reader("moe.load_max_over_mean").read(
        facts) == 2.5
    assert glm_scopes.share_of_busy(facts, "r2d2.torso") == pytest.approx(
        100.0 * 26_054 / 1_000_000)
    # the grouped-matmul kernels are found by name, not by scope
    facts["trace"]["devices"][0]["op_ns"] = {
        "ragged-dot-none.51 [custom-call]": 30_000,
        "ragged-dot-none.7 [custom-call]": 20_000,
        "ragged-dot-metadata [custom-call]": 5,
        "fusion.1 [convolution fusion] x.py:1": 900_000}
    assert glm_scopes.grouped_matmul_ns(facts) == 50_005
    monkeypatch.setattr(glm_scopes, "SCOPES", ("r2d2.torso", "glm.moe"))
    facts.pop("glm_scope_ns")
    facts["glm_scope_ns"] = {"glm.moe": 100_000}
    assert glm_scopes.share_of_busy(facts, "glm.moe") == pytest.approx(
        100.0 * 150_005 / 1_000_000)
    reader = cells.layer_metric_reader("kernels.moe_expert_mm_roofline")
    monkeypatch.setattr(cells, "layer_metric_reader", lambda name: type(
        "R", (), {"read": staticmethod(lambda f: 250.0)}))
    seconds = 50_005 / 1_000_000 * 0.250
    flop = 6 * 2048 * 1536 * (30_000 + 3 * 12_000)
    assert reader.read(facts) == pytest.approx(
        100.0 * flop / seconds / 197e12)


def test_the_references_rounder_is_reduce_precision_with_traced_bits():
    """One compiled graph serves every precision only if rounding on
    the bits equals `jax.lax.reduce_precision` at each of them."""
    import jax

    from benchmarks.reference.glm_moe_q import rounder

    rng = np.random.default_rng(0)
    x = jnp.asarray((rng.normal(size=50_000) * 10.0 ** rng.integers(
        -20, 20, 50_000)).astype(np.float32))
    rounded = jax.jit(lambda x, m: rounder(m)(x))
    for bits in (5, 6, 7, 10, 23):
        np.testing.assert_array_equal(
            rounded(x, bits), jax.lax.reduce_precision(x, 8, bits))
    assert rounder(None)(x) is x
