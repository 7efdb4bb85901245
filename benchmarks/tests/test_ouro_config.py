"""configs/ouro_2p6b_1chip.json against the preset and against the
catalog row it was drawn from, the parameter count from shapes, the
family's two FLOP counts by hand, the keys the accepted attention
reader takes held to the model's own, the new metrics' declarations -
and the traffic kind `looped_token_sequence_free_run` end to end at the
tiny preset's widths on the CPU, through `runner.run_cell`, `correct`
true as the cell runs and false under each departure the check must
refuse, made in the PROGRAM."""

import dataclasses
import json
import os
import time

import pytest

from benchmarks.harness import (cells, flops, flops_afmoe, flops_ouro,
                                runner)

CELL = "ouro_offline"
CONF = cells.resolve(CELL).config
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
HELD = CONF["num_hidden_layers"]
STEPS = 4
BLOCK = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
ENDS = 2 * 49_152 * 2048 + 2048 + 2049
NEW_METRICS = {
    "learner.loop_share": ("%", "higher", "device_trace", "learner"),
    "learner.dense_ffn_share": ("%", "lower", "device_trace", "learner"),
    "kernels.dense_ffn_mm_roofline": ("%", "higher", "device_trace",
                                      "kernels (XLA)"),
    "loop.block_applications": ("blocks", "higher", "program_counter",
                                "learner")}


def _cfg():
    from ape_x_dqn_tpu.configs import get_config
    from ape_x_dqn_tpu.runtime.train import apply_overrides

    return apply_overrides(get_config(CONF["preset"]), CONF["overrides"])


def test_model_sizes_are_what_preset_plus_overrides_build():
    cfg = _cfg()
    ou, m = cfg.network.ouro, CONF["model_sizes"]
    for key in ("hidden_size", "intermediate_size", "num_hidden_layers",
                "total_ut_steps", "early_exit_threshold",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "max_position_embeddings", "vocab_size", "rms_norm_eps",
                "rope_theta"):
        assert m[key] == getattr(ou, key), key
    from ape_x_dqn_tpu.models import build_network

    net = build_network(cfg.network, None)
    assert m["vocab_size"] == net.num_actions == CONF["sizes"]["num_actions"]
    assert m["parameters"] == net.param_count() == HELD * BLOCK + ENDS
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
        4_096, 1_024, 2_048)
    assert cfg.network.kind == "ouro_q" and cfg.replay.kind == "sequence"
    assert CONF["family"] == flops_ouro.FAMILY
    assert CONF["layout"]["layer_shared_by"] == 1
    assert "RE-ENTERED FOUR TIMES" in CONF["layout"]["deployment"]
    assert cfg.env.num_tokens == net.num_actions == 49_152
    assert cfg.replay.capacity == 8_192 and cfg.learner.batch_size == 1
    assert (cfg.learner.n_step, cfg.learner.gamma, cfg.learner.lr,
            cfg.learner.sample_chunk, cfg.learner.train_chunk) == (
        5, 0.99, 1e-4, 1, 2)


def test_the_keys_the_accepted_reader_takes_repeat_the_models_own():
    """`kernels.attn_flash_roofline` reads Trinity-Mini's key names; the
    file names one full layer per block APPLICATION under them, so the
    executed count holds the loop's four passes."""
    m = CONF["model_sizes"]
    assert m["layer_types"] == ["full_attention"] * (STEPS * HELD)
    assert len(m["layer_types"]) == flops_ouro.applications(m) \
        == m["total_ut_steps"] * m["num_hidden_layers"]
    assert m["vocab_held"] == m["vocab_size"]
    assert m["sliding_window"] is None and CONF["sliding_window"] is None
    # no expert layer: nothing routed, nothing shared
    assert (m["num_experts_per_tok"], m["experts_held"],
            m["num_shared_experts"], m["moe_intermediate_size"]) == (
        0, 0, 0, 0)
    assert m["num_dense_layers"] == len(m["layer_types"])
    pairs = flops_afmoe._pairs(m)
    n = STEPS * HELD
    assert pairs == {"burn": n * (1024 * 1025 // 2),
                     "cached": n * 1024 * 3072,
                     "new": n * (3072 * 3073 // 2)}
    per_pair = 4 * 128 * 16
    assert flops_afmoe.executed_attention_flops(1, m) == pytest.approx(
        per_pair * (2 * pairs["burn"] + 3 * (pairs["cached"] + pairs["new"])
                    + 2.5 * pairs["new"] + 1.5 * pairs["cached"]))


def test_the_parameter_count_by_hand():
    """A block: four 2048 x 2048 projections, three 2048 x 5632 MLP
    matrices, four norms; embedding + untied head 2 x 49,152 x 2048, the
    final norm, the gate's 2,049; and the published model whole."""
    assert BLOCK == 51_388_416 and ENDS == 201_330_689
    assert HELD * BLOCK + ENDS == CONF["model_sizes"]["parameters"]
    assert (48 * BLOCK + ENDS == 2_667_974_657
            == CONF["model_sizes"]["parameters_published"]
            == CONF["published"]["parameters"])
    assert CONF["published"]["num_hidden_layers"] == 48
    memory = CONF["memory"]
    # ISSUE 41's rule: the deepest of 6, 5, 4 that leaves 0.75 GiB
    fitting = [n for n in (6, 5, 4)
               if memory[f"{n}_layers"]["leaves_0.75_spare"]]
    assert max(fitting) == HELD
    for n in (6, 5, 4):
        r = memory[f"{n}_layers"]
        assert r["parameters"] == n * BLOCK + ENDS
        assert r["total"] == pytest.approx(
            r["arguments"] + r["temp"] + r["code"] + r["server_copy"],
            abs=2e-4)
        assert r["leaves_0.75_spare"] == (r["of"] - r["total"] >= 0.75)


def test_the_two_counts_by_hand():
    """A token through one block application: 33.55 M (four
    projections) + 69.21 M (the MLP) = 102.8 MFLOP, the head 201.3; 8,192
    a pair; every layer counted once per loop step."""
    m = CONF["model_sizes"]
    block, pair, head = flops_ouro.token_flops(m)
    assert block == 2 * 4 * 2048 * 2048 + 6 * 2048 * 5632
    assert block == pytest.approx(102.8e6, rel=1e-3)
    assert (pair, head) == (8_192, 2 * 2048 * 49_152)
    n = flops_ouro.applications(m)
    assert n == STEPS * HELD
    assert flops_ouro.causal_pairs(0, 1024) == 1024 * 1025 // 2
    assert flops_ouro.causal_pairs(1024, 3072) == (
        4096 * 4097 // 2 - 1024 * 1025 // 2)
    flops_ouro.register(m)
    got = flops.TRAIN_STEP_FLOPS[CONF["family"]](CONF["sizes"])
    assert got == pytest.approx(
        2 * (1024 * (n * block + head) + n * pair * (1024 * 1025 // 2))
        + 4 * (3072 * (n * block + head)
               + n * pair * (4096 * 4097 // 2 - 1024 * 1025 // 2)))
    # the loop is most of a forward pass's matmul FLOP whatever the
    # length
    assert n * block / (n * block + head) > 0.9
    assert flops_ouro.executed_dense_ffn_flops(1, m) == (
        n * (2 * 1024 + 5 * 3072) * 6 * 2048 * 5632)


def test_the_preset_is_the_published_model():
    from ape_x_dqn_tpu.configs import get_config

    ou = get_config(CONF["preset"]).network.ouro
    assert (ou.num_hidden_layers, ou.total_ut_steps, ou.vocab_size,
            ou.early_exit_threshold) == (48, 4, 49_152, 1.0)
    assert _cfg().network.ouro.num_hidden_layers == HELD
    assert _cfg().network.ouro.total_ut_steps == 4     # every step kept


def test_the_file_holds_the_catalog_rows_keys():
    """Every key of the catalog row's `config`, under the same name, at
    the same value - but `num_hidden_layers`, the ONE model key in
    `reduced`. `layer_types` is the published list, whole."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "Ouro-2.6B")
    assert CONF["source"].startswith(row["source_url"])
    assert "total_ut_steps 4" in CONF["source"]
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == CONF["name"])
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == CONF["reduced"] == [
        "num_hidden_layers", "actors.num_actors", "eval_every_steps",
        "eval_episodes", "total_env_frames"]
    assert set(CONF["reduced_why"]) == set(CONF["reduced"])
    for key, published in row["config"].items():
        if key == "num_hidden_layers":
            assert (published, CONF[key]) == (48, HELD)
            assert CONF["published"][key] == published
        else:
            assert CONF[key] == published, key
    m = CONF["model_sizes"]
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "rms_norm_eps",
                "rope_theta", "vocab_size", "max_position_embeddings",
                "total_ut_steps", "early_exit_threshold"):
        assert m[key] == row["config"][key], key
    # every (+) of the issue is stated
    assert sum(k.startswith("(+) ") for k in CONF["assumed"]) == 5


def test_overrides_are_the_reduced_keys():
    keys = [o.split("=")[0] for o in CONF["overrides"]]
    assert keys == ["network.ouro.num_hidden_layers", "actors.num_actors",
                    "eval_every_steps", "eval_episodes"]


def test_the_cell_and_its_metrics_are_declared():
    bench = cells.load_benchmark()
    cell = cells.resolve(CELL)
    assert (cell.chips, cell.config_name) == (1, "ouro_2p6b_1chip")
    assert bench["workloads"][-1]["traffic"] == "offline_tokens_4k"
    assert cell.traffic["kind"] == "looped_token_sequence_free_run"
    assert {m["name"] for m in cell.end_to_end} == {
        "learn_samples_per_s", "peak_hbm_gib", "setup_s"}
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, better, source, layer) in NEW_METRICS.items():
        m = declared[name]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (unit, better, source, layer,
                                "learn_samples_per_s")
        assert m["workloads"] == [CELL]
        assert callable(cells.layer_metric_reader(name).read)
    reported = {m["name"] for m in cell.per_layer}
    assert reported == set(NEW_METRICS) | {
        "learner.step_ms", "learner.mfu", "kernels.mxu_share",
        "device.idle_share", "learner.burn_in_share",
        "learner.attn_share", "learner.attn_full_share",
        "kernels.attn_flash_roofline", "learner.loss_grad_share",
        "learner.optimizer_share", "learner.health_share",
        "learner.cycle_unscoped_share", "setup.compile_s",
        "replay.fill_transitions_per_s"}
    # a net without experts is on none of the expert layer's lists
    assert not [n for n in reported if n.startswith("moe.")
                or n in ("learner.moe_share",
                         "kernels.moe_expert_mm_roofline")]
    mix = cell.traffic
    assert (mix["ring_fill"], mix["fill_sequences_per_add"],
            mix["token_zipf_exponent"], mix["priority_lognormal_sigma"],
            mix["terminal_one_in"], mix["episode_tail_one_in"],
            mix["reward_one_in"], mix["max_dispatches_in_flight"],
            mix["trace_window_s"]) == (
        1.0, 32, 1.0, 1.0, 32_768, 16, 64, 2, 4.0)


def test_the_new_readers_return_nothing_where_there_is_nothing():
    """What the parent's program gives them: no counter, no scope."""
    count = cells.layer_metric_reader("loop.block_applications")
    assert count.read({}) is None and count.read({"loop": {}}) is None
    assert count.read({"loop": {"block_applications": 20.0}}) == 20.0
    facts = {"ouro_scope_ns": {}, "trace": {"devices": [{"busy_ns": 10}]}}
    for name, scope in (("learner.loop_share", "ouro.loop"),
                        ("learner.dense_ffn_share", "ouro.mlp")):
        reader = cells.layer_metric_reader(name)
        facts["ouro_scope_ns"] = {}
        assert reader.read(facts) is None
        facts["ouro_scope_ns"] = {scope: 2}
        assert reader.read(facts) == 20.0


# -- the kind end to end on the CPU ------------------------------------------

# the tiny preset's widths (hidden 64, 4 ungrouped heads of 16, an MLP
# of 96, a vocabulary of 64, two layers run four times, 32-token
# sequences with a prefix of 12)
TINY = ("network.ouro.hidden_size=64",
        # the key-value heads first: each override is checked as it is set
        "network.ouro.num_key_value_heads=4",
        "network.ouro.num_attention_heads=4", "network.ouro.head_dim=16",
        "network.ouro.intermediate_size=96",
        "network.ouro.max_position_embeddings=32",
        "network.ouro.num_hidden_layers=2", "network.ouro.vocab_size=64",
        "env.num_tokens=64", "learner.batch_size=4", "replay.capacity=64",
        "replay.seq_length=32", "replay.burn_in=12",
        "replay.seq_overlap=16", "learner.n_step=2")
TRAFFIC = {"fill_sequences_per_add": 16, "episode_tail_one_in": 4,
           "terminal_one_in": 16, "reward_one_in": 4}
DEPARTURES = ("three_loop_steps_for_four", "every_step_reads_step_0_prefix",
              "final_norm_once_after_the_loop", "no_post_sublayer_norms")


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


def test_kind_tiny_is_correct_and_every_reading_that_must_fail_fails(
        monkeypatch, capsys):
    result, facts = _tiny_run(monkeypatch, show_limits=True)
    said = capsys.readouterr().err
    for name in DEPARTURES:
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
        "gradients_match_reference", "loop_counters_match_configuration",
        "tree_root_is_leaf_sum", "valid_frac_is_the_seeded_share",
        "every_loss_finite", "step_counter_closes"}
    assert facts["batch_size"] == 4 and facts["train_chunk"] == 2
    assert facts["fill"]["transitions"] == 64 * 32     # tokens stored
    assert facts["loop"]["block_applications"] == STEPS * 2
    assert 0.0 < facts["loop"]["exit_mass_last"] < 1.0
    assert "moe" not in facts
    assert facts["family"] == "ouro_looped_q"
    assert facts["family"] in flops.TRAIN_STEP_FLOPS
    # the gate's two leaves are the ones without a gradient
    assert "'grad_leaves_without_gradient': 2" in said


@pytest.mark.parametrize("departure", DEPARTURES)
def test_a_departure_in_the_program_turns_correct_false(monkeypatch,
                                                        departure):
    """The same departures made in the PROGRAM: the run as the cell
    makes it (no `show_limits`) comes out not correct."""
    import jax
    import jax.numpy as jnp

    from ape_x_dqn_tpu.models import ouro_q
    from ape_x_dqn_tpu.models.ouro_q import OuroQNet

    if departure == "three_loop_steps_for_four":
        real_scan = jax.lax.scan

        def three(f, init, xs, length):
            del length
            return real_scan(f, init, jax.tree.map(lambda a: a[:3], xs),
                             length=3)

        lax = type("Lax", (), {
            "scan": staticmethod(three),
            "__getattr__": lambda self, name: getattr(jax.lax, name)})()
        monkeypatch.setattr(ouro_q, "jax", type("Jax", (), {
            "lax": lax,
            "__getattr__": lambda self, name: getattr(jax, name)})())
    elif departure == "every_step_reads_step_0_prefix":
        real = OuroQNet.apply_with_stats

        def one_cache_a_layer(self, params, tokens, state=()):
            # the bug a looped model with a prefix cache invites: the
            # steps share one cache per layer (step 0's)
            state = tuple(
                (jnp.broadcast_to(k[:1], k.shape),
                 jnp.broadcast_to(v[:1], v.shape), seen)
                for k, v, seen in state)
            return real(self, params, tokens, state)

        monkeypatch.setattr(OuroQNet, "apply_with_stats", one_cache_a_layer)
    elif departure == "final_norm_once_after_the_loop":
        real_end, real_head = OuroQNet._end_of_step, OuroQNet._head

        def no_norm(self, params, x):
            return x, real_end(self, params, x)[1]

        def norm_then_head(self, params, x):
            return real_head(self, params, real_end(self, params, x)[0])

        monkeypatch.setattr(OuroQNet, "_end_of_step", no_norm)
        monkeypatch.setattr(OuroQNet, "_head", norm_then_head)
    else:
        real_norm, real_block = ouro_q._norm, OuroQNet._block
        after = []

        def block(self, p, x, cache, positions):
            after[:] = [p["post_attention_layernorm"],
                        p["post_mlp_layernorm"]]
            return real_block(self, p, x, cache, positions)

        def norm(x, g, eps):
            return x if any(g is a for a in after) else real_norm(x, g, eps)

        monkeypatch.setattr(OuroQNet, "_block", block)
        monkeypatch.setattr(ouro_q, "_norm", norm)
    result, facts = _tiny_run(monkeypatch)
    assert not result["correct"], departure
    assert not facts["checks"]["q_loss_and_priorities_match_reference"]
    assert facts["checks"]["every_loss_finite"]
    assert facts["checks"]["loop_counters_match_configuration"] == (
        departure != "three_loop_steps_for_four")
