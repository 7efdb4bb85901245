"""configs/smallthinker_21b_ep8_1chip.json against the preset and
against the catalog row it was drawn from, the parameter count from
shapes, the family's FLOP count by hand, the keys the accepted readers
take held to the model's own, the new metrics' declarations - and the
traffic kind `decoder_token_sequence_free_run` end to end at the tiny
preset's widths on the CPU, through `runner.run_cell`, `correct` true
as the cell runs and false under each perturbation the check must
refuse."""

import dataclasses
import json
import os
import time

import pytest

from benchmarks.harness import (cells, flops, flops_afmoe, flops_glm_moe,
                                flops_smallthinker, runner)

CELL = "smallthinker_offline"
CONF = cells.resolve(CELL).config
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": (52, 4), "moe_num_primary_experts": (64, 8),
           "vocab_size": (151_936, 18_992)}
NEW_METRICS = {"learner.route_ahead_share": ("lower", "device_trace"),
               "moe.compact_share": ("higher", "program_counter")}


def _cfg():
    from ape_x_dqn_tpu.configs import get_config
    from ape_x_dqn_tpu.runtime.train import apply_overrides

    return apply_overrides(get_config(CONF["preset"]), CONF["overrides"])


def test_model_sizes_are_what_preset_plus_overrides_build():
    cfg = _cfg()
    st, m = cfg.network.smallthinker, CONF["model_sizes"]
    for key in ("hidden_size", "moe_ffn_hidden_size", "num_hidden_layers",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "sliding_window_size", "max_position_embeddings",
                "moe_num_primary_experts", "moe_num_active_primary_experts",
                "moe_primary_router_apply_softmax", "norm_topk_prob",
                "vocab_size", "rms_norm_eps", "rope_theta", "shard_count",
                "shard_index", "force_balanced_routing"):
        assert m[key] == getattr(st, key), key
    assert m["rope_layout"] == list(st.rope_layout) == [0, 1, 1, 1]
    assert m["sliding_window_layout"] == list(st.sliding_window_layout) \
        == [0, 1, 1, 1]
    from ape_x_dqn_tpu.models import build_network

    net = build_network(cfg.network, None)
    assert m["experts_held"] == net.experts_held == 8
    assert m["vocab_held"] == net.num_actions == CONF["sizes"]["num_actions"]
    assert m["parameters"] == net.param_count() == 370_547_200
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
    assert (m["seq_length"], m["burn_in"], m["seq_overlap"]) == (
        16_384, 4_096, 8_192)
    assert m["seq_length"] == st.max_position_embeddings
    assert cfg.network.kind == "smallthinker_q"
    assert cfg.replay.kind == "sequence"
    assert CONF["family"] == flops_smallthinker.FAMILY
    assert CONF["layout"]["layer_shared_by"] == st.shard_count == 8
    assert cfg.env.num_tokens == net.num_actions
    assert cfg.replay.capacity == 2048 and cfg.learner.batch_size == 1


def test_the_keys_the_accepted_readers_take_repeat_the_models_own():
    """`kernels.attn_flash_roofline` and `kernels.moe_expert_mm_roofline`
    read Trinity-Mini's and GLM's key names; the file repeats this
    model's numbers under them."""
    m = CONF["model_sizes"]
    for theirs, own in (("moe_intermediate_size", "moe_ffn_hidden_size"),
                        ("sliding_window", "sliding_window_size"),
                        ("num_experts", "moe_num_primary_experts"),
                        ("num_experts_per_tok",
                         "moe_num_active_primary_experts")):
        assert m[theirs] == m[own], theirs
    assert m["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3
    assert [k == "sliding_attention" for k in m["layer_types"]] \
        == [bool(w) for w in m["sliding_window_layout"]]
    assert (m["num_shared_experts"], m["num_dense_layers"],
            m["intermediate_size"]) == (0, 0, 0)
    # both executed counts come out of the file alone
    pairs = flops_afmoe._pairs(m)
    assert pairs == {"burn": 4 * (4096 * 4097 // 2),
                     "cached": 4096 * 12_288 + 3 * (4095 * 4096 // 2),
                     "new": (12_288 * 12_289 // 2
                             + 3 * (12_288 * 4096 - 4095 * 4096 // 2))}
    assert sum(pairs.values()) == 310_392_832
    per_pair = 4 * 128 * 28
    assert flops_afmoe.executed_attention_flops(1, m) == pytest.approx(
        per_pair * (2 * pairs["burn"] + 3 * (pairs["cached"] + pairs["new"])
                    + 2.5 * pairs["new"] + 1.5 * pairs["cached"]))
    assert flops_glm_moe.executed_expert_flops(100.0, 10.0, m) == \
        6 * 2560 * 768 * 130.0


def test_the_parameter_count_by_hand():
    """Attention 20,971,520 (q, o of 2560 x 3584; k, v of 2560 x 512),
    router 2560 x 64, two norms, each routed expert 3 x 2560 x 768,
    embedding + head 2 x 18,992 x 2560, final norm; and the published
    model whole."""
    attention = 2 * 2560 * 3584 + 2 * 2560 * 512
    assert attention == 20_971_520
    expert = 3 * 2560 * 768
    assert expert == 5_898_240
    layer = attention + 2560 * 64 + 2 * 2560 + 8 * expert
    assert layer == 68_326_400
    assert (4 * layer + 2 * 18_992 * 2560 + 2560
            == CONF["model_sizes"]["parameters"] == 370_547_200)
    whole_layer = attention + 2560 * 64 + 2 * 2560 + 64 * expert
    assert (52 * whole_layer + 2 * 151_936 * 2560 + 2560
            == CONF["model_sizes"]["parameters_published"]
            == CONF["published"]["parameters"])
    assert CONF["published"] == {
        "num_hidden_layers": 52, "moe_num_primary_experts": 64,
        "vocab_size": 151_936, "parameters": 21_506_562_560}
    assert CONF["memory"]["this_cut_8_experts_4_layers"]["parameters"] \
        == 370_547_200


def test_the_model_count_by_hand():
    """301.7 MFLOP a token outside the pairs, 14,336 a pair, 310.4 M
    pairs a sequence: 34.13 TFLOP a step."""
    m = CONF["model_sizes"]
    rest, pair, head = flops_smallthinker.token_flops(m)
    assert rest == 4 * (2 * (2 * 2560 * 3584 + 2 * 2560 * 512)
                        + 2 * 2560 * 64 + 6 * 2560 * 768 * 6 * 8 / 64)
    assert (pair, head) == (14_336, 2 * 2560 * 18_992)
    assert rest + head == pytest.approx(301.7e6, rel=1e-3)
    flops_smallthinker.register(m)
    got = flops.TRAIN_STEP_FLOPS[CONF["family"]](CONF["sizes"])
    burn, trained = 4 * (4096 * 4097 // 2), 310_392_832 - 4 * (
        4096 * 4097 // 2)
    assert got == pytest.approx(
        2 * (4096 * (rest + head) + pair * burn)
        + 4 * (12_288 * (rest + head) + pair * trained))
    assert got == pytest.approx(34.13e12, rel=1e-3)


def test_the_preset_is_the_published_model():
    from ape_x_dqn_tpu.configs import get_config

    st = get_config(CONF["preset"]).network.smallthinker
    assert (st.num_hidden_layers, st.moe_num_primary_experts, st.vocab_size,
            st.shard_count) == (52, 64, 151_936, 1)
    assert list(st.rope_layout) == CONF["rope_layout"]
    assert list(st.sliding_window_layout) == CONF["sliding_window_layout"]
    assert not st.force_balanced_routing
    assert _cfg().network.smallthinker.force_balanced_routing
    assert "force_balanced_routing" in CONF["assumed"]["routing"]


def test_the_file_holds_the_catalog_rows_keys():
    """Every key of the catalog row's `config`, under the same name, at
    the same value - but the three `reduced` names, which give what is
    held here. The two layouts are the published lists, whole; the four
    layers held are `model_sizes`', the first period."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert CONF["source"].startswith(row["source_url"])
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == CONF["name"])
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == CONF["reduced"]
    assert set(CONF["reduced_why"]) == set(CONF["reduced"])
    for key, published in row["config"].items():
        if key in REDUCED:
            assert (published, CONF[key]) == REDUCED[key], key
            assert key in CONF["reduced"]
            assert CONF["published"][key] == published
        else:
            assert CONF[key] == published, key
    m = CONF["model_sizes"]
    for key in ("hidden_size", "moe_ffn_hidden_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "sliding_window_size",
                "moe_num_active_primary_experts", "rms_norm_eps",
                "rope_theta", "moe_num_primary_experts", "vocab_size",
                "max_position_embeddings"):
        assert m[key] == row["config"][key], key
    assert m["rope_layout"] == row["config"]["rope_layout"][:4]
    assert m["sliding_window_layout"] == \
        row["config"]["sliding_window_layout"][:4]
    # every (+) of the issue is stated, the unattested one in its words
    assert sum(k.startswith("(+) ") for k in CONF["assumed"]) == 6
    assert "not checked here" in CONF["assumed"]["(+) router_reads"]


def test_overrides_are_the_reduced_keys_and_the_share():
    keys = [o.split("=")[0] for o in CONF["overrides"]]
    assert keys == ["network.smallthinker.num_hidden_layers",
                    "network.smallthinker.rope_layout",
                    "network.smallthinker.sliding_window_layout",
                    "network.smallthinker.shard_count", "env.num_tokens",
                    "actors.num_actors", "eval_every_steps", "eval_episodes",
                    "network.smallthinker.force_balanced_routing"]


def test_the_cell_and_its_metrics_are_declared():
    bench = cells.load_benchmark()
    cell = cells.resolve(CELL)
    assert (cell.chips, cell.config_name) == (1, "smallthinker_21b_ep8_1chip")
    assert cell.traffic["kind"] == "decoder_token_sequence_free_run"
    assert {m["name"] for m in cell.end_to_end} == {
        "learn_samples_per_s", "peak_hbm_gib", "setup_s"}
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name, (better, source) in NEW_METRICS.items():
        m = declared[name]
        assert (m["better"], m["source"], m["unit"], m["moves"]) == (
            better, source, "%", "learn_samples_per_s")
        assert m["workloads"] == [CELL]
        assert callable(cells.layer_metric_reader(name).read)
    reported = {m["name"] for m in cell.per_layer}
    assert reported >= set(NEW_METRICS) | {
        "learner.step_ms", "learner.mfu", "kernels.mxu_share",
        "device.idle_share", "learner.burn_in_share", "learner.moe_share",
        "moe.load_max_over_mean", "kernels.moe_expert_mm_roofline",
        "learner.attn_share", "learner.attn_full_share",
        "kernels.attn_flash_roofline", "learner.loss_grad_share",
        "learner.optimizer_share", "learner.health_share",
        "learner.cycle_unscoped_share", "setup.compile_s",
        "replay.fill_transitions_per_s"}
    mix = cell.traffic
    assert (mix["fill_sequences_per_add"], mix["terminal_one_in"],
            mix["episode_tail_one_in"], mix["reward_one_in"],
            mix["max_dispatches_in_flight"], mix["trace_window_s"]) == (
        8, 131_072, 16, 64, 2, 4.0)


def test_the_new_readers_return_nothing_where_there_is_nothing():
    """What the parent's program gives them: no counter, no scope."""
    compact = cells.layer_metric_reader("moe.compact_share")
    assert compact.read({}) is None and compact.read({"moe": {}}) is None
    assert compact.read({"moe": {"compact_share": 0.75}}) == 75.0
    ahead = cells.layer_metric_reader("learner.route_ahead_share")
    facts = {"st_scope_ns": {}, "trace": {"devices": [{"busy_ns": 10}]}}
    assert ahead.read(facts) is None
    facts["st_scope_ns"] = {"st.route_ahead": 2}
    assert ahead.read(facts) == 20.0


# -- the kind end to end on the CPU ------------------------------------------

# the tiny preset's widths (hidden 64, 14 / 2 heads of 16: 7 queries a
# key head; 8 experts top-3 of which 4 are held, 32 of 64 vocabulary
# rows, a window of 8 inside 32-token sequences, global + 3 sliding)
TINY = ("network.smallthinker.hidden_size=64",
        "network.smallthinker.num_key_value_heads=2",
        "network.smallthinker.num_attention_heads=14",
        "network.smallthinker.head_dim=16",
        "network.smallthinker.moe_ffn_hidden_size=32",
        "network.smallthinker.sliding_window_size=8",
        "network.smallthinker.max_position_embeddings=32",
        # the share first: each override is checked as it is set
        "network.smallthinker.shard_count=2",
        "network.smallthinker.moe_num_primary_experts=8",
        "network.smallthinker.moe_num_active_primary_experts=3",
        "network.smallthinker.vocab_size=64", "env.num_tokens=32",
        "learner.batch_size=4", "replay.capacity=64",
        "replay.seq_length=32", "replay.burn_in=12",
        "replay.seq_overlap=16", "learner.n_step=2")
TRAFFIC = {"fill_sequences_per_add": 16, "episode_tail_one_in": 4,
           "terminal_one_in": 16, "reward_one_in": 4}


class _Clock:
    """`time` for the kind's window loop: a tenth of a second a call, so
    a window of one second is nine dispatches on any machine."""

    def __init__(self):
        self.now = 0.0

    def monotonic(self) -> float:
        self.now += 0.1
        return self.now


def _tiny_run(monkeypatch, **mix) -> tuple[dict, dict]:
    import jax

    from benchmarks.harness import token_sequence_checks as limits

    jax.config.update("jax_enable_compilation_cache", False)
    # as test_run_afmoe_cpu.py: at these widths a norm gain is a leaf of
    # 64 values and ratios of two such norms swing
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

        def run(rt):
            facts.update(kind.run(rt))
            return facts
        return type("SpiedKind", (), {"run": staticmethod(run)})

    monkeypatch.setattr(cells, "traffic_kind", spying)
    result = runner.run_cell(cell, seed=2147483900, seconds=1.0,
                             trace=False, t_process_start=time.monotonic(),
                             devices=jax.devices()[:1], cfg_overrides=TINY)
    return result, facts


def _scaled_routers(monkeypatch):
    """Logits of order 1, as at the published hidden size (0.02 x
    sqrt(2560)): at hidden 64 every scoring gives nearly uniform
    weights and no departure of the router could be told apart."""
    from ape_x_dqn_tpu.models import expert_layer

    real = expert_layer.seeded_params

    def seeded(shapes, key):
        params = real(shapes, key)
        for layer in params["layers"]:
            layer["mlp"]["gate"] = layer["mlp"]["gate"] * 6.0
        return params

    monkeypatch.setattr(
        "ape_x_dqn_tpu.models.smallthinker_q.seeded_params", seeded)


def test_kind_tiny_is_correct_and_every_reading_that_must_fail_fails(
        monkeypatch, capsys):
    _scaled_routers(monkeypatch)
    result, facts = _tiny_run(monkeypatch, show_limits=True)
    said = capsys.readouterr().err
    for name in ("window_ignored", "silu_for_relu",
                 "router_fed_from_expert_input",
                 "sigmoid_normalised_weights"):
        assert f"'{name}': {{'passes': False" in said, name
    assert "'one_bit_less': {'passes': " in said
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert set(result["metrics"]) == {"learn_samples_per_s", "peak_hbm_gib",
                                      "setup_s"}
    json.dumps(result)
    assert result["correct"] and result["failed"] == 0, facts["checks"]
    assert result["attempted"] == facts["grad_steps"] > 0
    assert set(facts["checks"]) == {
        "sequences_are_what_was_written",
        "q_loss_and_priorities_match_reference",
        "gradients_match_reference",
        "routing_matches_reference_outside_margin",
        "moe_rows_counter_matches_selection", "tree_root_is_leaf_sum",
        "valid_frac_is_the_seeded_share", "every_loss_finite",
        "step_counter_closes"}
    assert facts["batch_size"] == 4 and facts["train_chunk"] == 2
    assert facts["fill"]["transitions"] == 64 * 32     # tokens stored
    moe = facts["moe"]
    assert 0 < moe["rows_grad_per_step"] < moe["rows_per_step"]
    assert moe["load_max_over_mean"] >= 1.0
    assert 0.0 <= moe["compact_share"] <= 1.0
    assert facts["family"] == "smallthinker_swa_q"
    assert facts["family"] in flops.TRAIN_STEP_FLOPS


class _Over:
    """`real` with some attributes replaced."""

    def __init__(self, real, **over):
        self._real, self._over = real, over

    def __getattr__(self, name):
        over = self.__dict__["_over"]
        return over[name] if name in over else getattr(self._real, name)


@pytest.mark.parametrize("perturbation", [
    "silu_for_relu", "router_reads_the_experts_rows", "sigmoid_weights",
    "window_ignored"])
def test_a_perturbed_program_turns_correct_false(monkeypatch, capsys,
                                                 perturbation):
    """The same departures made in the PROGRAM: the run as the cell
    makes it (no `show_limits`) comes out not correct."""
    from ape_x_dqn_tpu.models import expert_layer, smallthinker_q, windowed_gqa

    import jax

    def seen_by(module, **nn):
        # `module`'s own name `jax` with jax.nn.<name> replaced: the
        # program is perturbed, the reference (which reads the real
        # jax.nn at call time) is not
        monkeypatch.setattr(module, "jax", _Over(jax, nn=_Over(jax.nn, **nn)))

    _scaled_routers(monkeypatch)
    if perturbation == "silu_for_relu":
        seen_by(smallthinker_q, relu=jax.nn.silu)
    elif perturbation == "router_reads_the_experts_rows":
        real = smallthinker_q.expert_ffn

        def planned_from_its_rows(p, y, dt, share, planned, act):
            # the expert layer's default: the plan made from the rows it
            # is fed, N2(h) (the model's own selection of those logits)
            del planned
            return real(p, y, dt, share, planned=smallthinker_q.plan(
                p, y.reshape(-1, y.shape[-1]), share,
                scoring=smallthinker_q.SOFTMAX_SELECTED), act=act)

        monkeypatch.setattr(smallthinker_q, "expert_ffn",
                            planned_from_its_rows)
    elif perturbation == "sigmoid_weights":
        def sigmoid_normalised(x, axis=-1):
            s = jax.nn.sigmoid(x)
            return s / s.sum(axis=axis, keepdims=True)

        seen_by(expert_layer, softmax=sigmoid_normalised)
    else:
        real_attend = windowed_gqa.attend
        monkeypatch.setattr(
            windowed_gqa, "attend",
            lambda q, k, v, cache, window, blocks, **kw: real_attend(
                q, k, v, cache, None, blocks, **kw))
        monkeypatch.setattr(
            windowed_gqa, "extend",
            lambda cache, k, v, window, real=windowed_gqa.extend: real(
                cache, k, v, None))
    result, facts = _tiny_run(monkeypatch)
    assert not result["correct"], perturbation
    assert not facts["checks"]["q_loss_and_priorities_match_reference"]
    assert facts["checks"]["every_loss_finite"]
