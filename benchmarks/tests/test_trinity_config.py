"""configs/trinity_mini_ep16_1chip.json against the preset and against
the catalog row it was drawn from, the parameter count from
`param_shapes`, the family's FLOP and pair counts against a brute-force
mask and a hand count, the seeded token content at this cell's lengths,
and the three new readers on a recorded trace."""

import json
import os

import numpy as np
import pytest

from benchmarks.harness import afmoe_scopes, cells, flops, flops_afmoe
from benchmarks.harness import token_content as tc

CELL = "trinity_mini_offline"
CONF = cells.resolve(CELL).config
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
REDUCED = {"num_hidden_layers": (32, 5), "num_dense_layers": (2, 1),
           "num_experts": (128, 8), "vocab_size": (200_192, 25_024)}
HELD_KINDS = ["sliding_attention"] * 4 + ["full_attention"]


def _cfg():
    from ape_x_dqn_tpu.configs import get_config
    from ape_x_dqn_tpu.runtime.train import apply_overrides

    return apply_overrides(get_config(CONF["preset"]), CONF["overrides"])


def test_model_sizes_are_what_preset_plus_overrides_build():
    cfg = _cfg()
    afmoe, m = cfg.network.afmoe, CONF["model_sizes"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_hidden_layers", "num_dense_layers",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "sliding_window", "num_experts", "num_shared_experts",
                "num_experts_per_tok", "route_norm", "route_scale",
                "mup_enabled", "vocab_size", "rms_norm_eps", "rope_theta",
                "shard_count", "shard_index", "vocab_shard_count",
                "force_balanced_routing"):
        assert m[key] == getattr(afmoe, key), key
    assert m["layer_types"] == list(afmoe.layer_types) == HELD_KINDS
    from ape_x_dqn_tpu.models import build_network

    net = build_network(cfg.network, None)
    assert m["experts_held"] == net.experts_held == 8
    assert m["vocab_held"] == net.num_actions == CONF["sizes"]["num_actions"]
    assert m["parameters"] == net.param_count() == 504_147_712
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
    assert (m["seq_length"], m["burn_in"]) == (8192, 2048)
    assert cfg.network.kind == "afmoe_q" and cfg.replay.kind == "sequence"
    assert CONF["family"] == flops_afmoe.FAMILY
    assert CONF["layout"]["layer_shared_by"] == afmoe.shard_count == 16
    assert cfg.env.num_tokens == net.num_actions
    assert cfg.replay.capacity == 4096 and cfg.learner.batch_size == 2


def test_the_parameter_count_by_hand():
    """Attention 27,263,232 (q, gate, o of 2048 x 4096; k, v of 2048 x
    512; two head norms of 128), four norms 8,192, dense FFN 3 x 2048 x
    6144, router 2048 x 128 + 128, shared and each routed expert 3 x
    2048 x 1024, embedding + head 2 x 25,024 x 2048, final norm."""
    attention = 3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128
    assert attention == 27_263_232
    expert = 3 * 2048 * 1024
    dense_block = attention + 4 * 2048 + 3 * 2048 * 6144
    expert_block = attention + 4 * 2048 + 2048 * 128 + 128 + 9 * expert
    assert (dense_block, expert_block) == (65_020_160, 84_156_800)
    assert (dense_block + 4 * expert_block + 2 * 25_024 * 2048 + 2048
            == CONF["model_sizes"]["parameters"])


def test_the_preset_is_the_published_model():
    from ape_x_dqn_tpu.configs import get_config

    afmoe = get_config(CONF["preset"]).network.afmoe
    assert (afmoe.num_hidden_layers, afmoe.num_dense_layers,
            afmoe.num_experts, afmoe.vocab_size, afmoe.shard_count,
            afmoe.vocab_shard_count) == (32, 2, 128, 200_192, 1, 0)
    assert list(afmoe.layer_types) == CONF["layer_types"]
    assert CONF["model_sizes"]["num_hidden_layers_published"] == 32
    assert not afmoe.force_balanced_routing
    assert _cfg().network.afmoe.force_balanced_routing
    assert "force_balanced_routing" in CONF["assumed"]["routing"]


def test_the_file_holds_the_catalog_rows_keys():
    """Every key of the catalog row's `config`, under the same name, at
    the same value - but the four `reduced` names, which give what is
    held here. `layer_types` is the published list, whole; the kinds of
    the five layers held are `model_sizes.layer_types`, one whole
    period after the dense layer."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "Trinity-Mini")
    assert CONF["source"].startswith(row["source_url"])
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == CONF["name"])
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == CONF["reduced"]
    for key, published in row["config"].items():
        if key in REDUCED:
            assert (published, CONF[key]) == REDUCED[key], key
            assert key in CONF["reduced"]
        else:
            assert CONF[key] == published, key
    m = CONF["model_sizes"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "sliding_window", "num_experts_per_tok",
                "num_shared_experts", "route_scale", "route_norm",
                "mup_enabled", "rms_norm_eps", "rope_theta", "num_experts",
                "vocab_size"):
        assert m[key] == row["config"][key], key
    period = row["config"]["global_attn_every_n_layers"]
    assert m["layer_types"][1:] == row["config"]["layer_types"][:period]
    # every (+) of the issue is stated
    assert sum(k.startswith("(+) ") for k in CONF["assumed"]) == 5


def test_overrides_are_the_reduced_keys_and_the_share():
    keys = [o.split("=")[0] for o in CONF["overrides"]]
    assert keys == ["network.afmoe.num_hidden_layers",
                    "network.afmoe.num_dense_layers",
                    "network.afmoe.layer_types",
                    "network.afmoe.shard_count",
                    "network.afmoe.vocab_shard_count", "env.num_tokens",
                    "actors.num_actors", "eval_every_steps",
                    "eval_episodes", "network.afmoe.force_balanced_routing"]
    assert CONF["reduced"][4:] == keys[6:9] + ["total_env_frames"]
    assert set(cells.resolve(CELL).traffic) - {"kind", "why"} == {
        "ring_fill", "fill_sequences_per_add", "token_zipf_exponent",
        "priority_lognormal_sigma", "terminal_one_in",
        "episode_tail_one_in", "reward_one_in", "max_dispatches_in_flight",
        "trace_window_s"}


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
@pytest.mark.parametrize("first,count,window,cached", [
    (0, 12, 8, 0), (12, 20, 8, 12), (5, 9, 3, 7), (0, 7, 16, 0)])
def test_pair_count_against_a_brute_force_mask(kind, first, count, window,
                                               cached):
    q = np.arange(first, first + count)[:, None]
    s = np.arange(first + count)[None, :]
    vis = s <= q
    if kind == "sliding_attention":
        vis &= q - s < window
    want = (int(vis[:, :cached].sum()), int(vis[:, cached:].sum()))
    assert flops_afmoe.admitted_pairs(kind, first, count, window,
                                      cached) == want


def test_flops_against_a_hand_count_at_the_published_widths():
    """Per token, outside the pairs: five layers of projections 2 x
    27,262,976 MACs; one dense FFN 3 x 2048 x 6144; four expert layers
    of router 2048 x 128 + 1.5 experts (one shared, 8 x 8 / 128 routed)
    of 3 x 2048 x 1024; head 2048 x 25,024. Pairs of one sequence: a
    sliding layer 2048 x 2049 / 2 in the prefix and 6144 x 2048 in the
    trained segment; the full layer 2048 x 2049 / 2 and 6144 x 2048 +
    6144 x 6145 / 2."""
    m = CONF["model_sizes"]
    rest, pair, head = flops_afmoe._layer_flops(m)
    assert rest == 2.0 * (5 * 27_262_976 + 3 * 2048 * 6144
                          + 4 * (2048 * 128 + 1.5 * 3 * 2048 * 1024))
    assert (pair, head) == (4.0 * 128 * 32, 2.0 * 2048 * 25_024)
    tri = lambda n: n * (n + 1) // 2                       # noqa: E731
    pairs = flops_afmoe._pairs(m)
    assert pairs["burn"] == 5 * tri(2048)
    assert pairs["cached"] + pairs["new"] == (
        4 * 6144 * 2048 + 6144 * 2048 + tri(6144))
    # keys in the cache: the full layer all 2,048 for every query; a
    # sliding layer 2,047 for the first trained query, one fewer each
    assert pairs["cached"] == 6144 * 2048 + 4 * tri(2047)
    flops_afmoe.register(m)
    got = flops.TRAIN_STEP_FLOPS[CONF["family"]](CONF["sizes"])
    want = 2 * (2.0 * (2048 * (rest + head) + pair * pairs["burn"])
                + 4.0 * (6144 * (rest + head)
                         + pair * (pairs["cached"] + pairs["new"])))
    assert got == pytest.approx(want, rel=1e-12)
    assert got == pytest.approx(41.698e12, rel=1e-4)
    executed = flops_afmoe.executed_attention_flops(2, m)
    assert executed == 2 * pair * (
        2.0 * pairs["burn"] + 3.0 * (pairs["cached"] + pairs["new"])
        + 2.5 * pairs["new"] + 1.5 * pairs["cached"])
    assert executed == pytest.approx(14.741e12, rel=1e-4)


def test_token_content_at_this_cells_lengths():
    cfg = _cfg()

    class Spec:
        num_actions = 25_024

    content = tc.content(cfg, Spec, 2**31 + 977, cells.resolve(CELL).traffic)
    items = tc.sequences(np, content, np.arange(256, dtype=np.int32))
    assert items["obs"].shape == (256, 8192)
    n_valid = items["mask"].sum(axis=1)
    tails = n_valid < 8192
    assert 0.02 < tails.mean() < 0.12                  # one in 16
    assert n_valid[tails].min() >= 2049 and n_valid[tails].max() <= 8191
    assert 0 <= items["obs"].min() and items["obs"].max() < 25_024
    # a terminal per 65,536 tokens beside the tails' own
    mid = items["terminals"].sum() - tails.sum()
    assert 10 <= mid <= 70
    np.testing.assert_array_equal(items["actions"][0, :-1],
                                  items["obs"][0, 1:])


def _facts(path, **more):
    class Rt:
        cell = cells.resolve(CELL)
        devices = [type("D", (), {"device_kind": "TPU v5 lite"})]

        @staticmethod
        def newest_xplane():
            return path

    return {"runtime": Rt, "train_chunk": 2, "batch_size": 2,
            "trace": {"devices": [{
                "busy_ns": 1_000_000,
                "modules": {"jit_train_many(1)": {
                    "median_ns": 2_000_000_000}}}]},
            **more}


NEW_READERS = ("learner.attn_share", "learner.attn_full_share",
               "kernels.attn_flash_roofline")


def test_the_new_metrics_are_declared_for_this_cell_alone():
    bench = cells.load_benchmark()
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert declared[name]["workloads"] == [CELL]
        assert declared[name]["moves"] == "learn_samples_per_s"
    assert [m["name"] for m in bench["per_layer"][-3:]] == list(NEW_READERS)
    reported = {m["name"] for m in cells.resolve(CELL).per_layer}
    assert reported == set(NEW_READERS) | {
        "setup.compile_s", "replay.fill_transitions_per_s",
        "learner.step_ms", "learner.mfu", "kernels.mxu_share",
        "device.idle_share", "learner.burn_in_share", "learner.moe_share",
        "moe.load_max_over_mean", "kernels.moe_expert_mm_roofline"}


def test_readers_find_nothing_in_a_program_without_the_scopes():
    """What the parent commit gives: ops with name stacks but no
    `afmoe.` scope. Every new reader returns nothing and does not
    raise."""
    facts = _facts(os.path.join(DATA, "scope_probe.xplane.pb"))
    for name in NEW_READERS:
        assert cells.layer_metric_reader(name).read(facts) is None, name
    assert set(facts["afmoe_scope_ns"]) == set(afmoe_scopes.SCOPES)
    assert not any(facts["afmoe_scope_ns"].values())


def test_readers_on_a_recorded_trace(monkeypatch):
    """scope_probe.xplane.pb's two scopes stand in for the attention's
    (the walk is scope_stats.py's, held by its own test): the shares
    are self time over busy time, and the roofline share is executed
    FLOP over the two kernel scopes' time per step over the peak."""
    probe = os.path.join(DATA, "scope_probe.xplane.pb")
    monkeypatch.setattr(afmoe_scopes, "SCOPES",
                        ("r2d2.torso", "r2d2.lstm_scan"))
    facts = _facts(probe)
    assert afmoe_scopes.share_of_busy(facts, "r2d2.torso") == pytest.approx(
        100.0 * 26_054 / 1_000_000)
    facts["afmoe_scope_ns"] = {"afmoe.attn": 600_000,
                               "afmoe.attn.sliding": 300_000,
                               "afmoe.attn.full": 200_000}
    read = lambda name: cells.layer_metric_reader(name).read(facts)  # noqa
    assert read("learner.attn_share") == pytest.approx(60.0)
    assert read("learner.attn_full_share") == pytest.approx(20.0)
    # step 1,000 ms (a dispatch of two is 2 s), half of busy time in the
    # two kernel scopes: 0.5 s a step for 14.741 TFLOP
    assert read("kernels.attn_flash_roofline") == pytest.approx(
        100.0 * 14.741e12 / 0.5 / 197e12, rel=1e-4)
