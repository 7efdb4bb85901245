"""configs/lfm2_24b_ep8_1chip.json against the preset and against the
catalog row it was drawn from, the parameter count from shapes, the
family's two counts by hand, the new metrics' declarations (by
PRESENCE: a later PR appends) - and the traffic kind
`conv_token_sequence_free_run` end to end at the tiny preset's widths
on the CPU, through `runner.run_cell`, `correct` true as the cell runs
and false under each departure the check must refuse and under one bit
less, made in the PROGRAM."""

import dataclasses
import json
import os
import time

import pytest

from benchmarks.harness import cells, flops, flops_glm_moe, flops_lfm2, runner
from benchmarks.harness.peaks import peaks_for

CELL = "lfm2_moe_offline"
CONF = cells.resolve(CELL).config
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": (40, 5), "num_dense_layers": (2, 1),
           "num_experts": (64, 8), "vocab_size": (65_536, 8_192)}
NEW_METRICS = {
    "learner.conv_share": ("%", "lower", "device_trace", "learner"),
    "learner.conv_mix_share": ("%", "lower", "device_trace", "learner"),
    "conv.positions_mixed": ("positions", "higher", "program_counter",
                             "learner"),
    "kernels.short_conv_roofline": ("%", "higher", "device_trace",
                                    "kernels (XLA)")}
HELD_KINDS = ["conv", "full_attention", "conv", "conv", "conv"]
# ISSUE 50's counts
CONV_OPERATOR = 2048 * 6144 + 3 * 2048 + 2048 * 2048
ATTN_OPERATOR = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
EXPERT = 3 * 2048 * 1536
DENSE = 3 * 2048 * 11776


def _cfg():
    from ape_x_dqn_tpu.configs import get_config
    from ape_x_dqn_tpu.runtime.train import apply_overrides

    return apply_overrides(get_config(CONF["preset"]), CONF["overrides"])


def test_model_sizes_are_what_preset_plus_overrides_build():
    cfg = _cfg()
    lf, m = cfg.network.lfm2_moe, CONF["model_sizes"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_hidden_layers", "num_dense_layers", "conv_L_cache",
                "conv_bias", "num_attention_heads", "num_key_value_heads",
                "rope_theta", "max_position_embeddings", "num_experts",
                "num_experts_per_tok", "norm_topk_prob",
                "routed_scaling_factor", "use_expert_bias", "vocab_size",
                "norm_eps", "tie_embedding", "shard_count", "shard_index",
                "vocab_shard_count", "force_balanced_routing"):
        assert m[key] == getattr(lf, key), key
    from ape_x_dqn_tpu.models import build_network
    from ape_x_dqn_tpu.runtime.family import reads_by_column

    net = build_network(cfg.network, None)
    assert m["layer_types"] == list(lf.layer_types) == HELD_KINDS
    assert lf.head_dim == 0 and m["head_dim"] == net.head_dim == 64
    assert m["experts_held"] == net.experts_held == 8
    assert m["vocab_held"] == net.num_actions == CONF["sizes"]["num_actions"]
    assert m["head_by_column"] == reads_by_column(net)
    assert m["parameters"] == net.param_count() == 469_285_248
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
    assert cfg.network.kind == "lfm2_moe_q"
    assert cfg.replay.kind == "sequence"
    assert CONF["family"] == flops_lfm2.FAMILY
    assert CONF["layout"]["layer_shared_by"] == lf.shard_count == 8
    assert CONF["layout"]["vocabulary_shared_by"] == 8
    assert cfg.env.num_tokens == net.num_actions
    assert cfg.replay.capacity == 2048 and cfg.learner.batch_size == 2
    # the batch was taken by ISSUE 50's rule from the compiled memory
    mem = CONF["memory"]
    taken = mem["sequence_16384_burn_4096_batch_2"]
    assert mem["of"] - taken["total"] >= 0.75
    assert taken["parameters"] == 469_285_248
    assert taken["total"] == pytest.approx(
        taken["arguments"] + taken["temp"] + taken["code"]
        + taken["server_copy"], abs=2e-4)
    dense = mem["sequence_16384_burn_4096_batch_2_dense_read"]
    assert 0.0 < taken["temp"] - dense["temp"] < 3 * 0.0625   # no flip


def test_the_keys_the_accepted_expert_reader_takes_are_the_models_own():
    """`kernels.moe_expert_mm_roofline` reads GLM's key names, which
    are this model's own: widths 2048 x 1536."""
    m = CONF["model_sizes"]
    assert flops_glm_moe.executed_expert_flops(100.0, 10.0, m) == \
        6 * 2048 * 1536 * 130.0


def test_the_parameter_count_by_hand():
    """A conv operator 16,783,360 (W_in 2048 x 6144, three taps a
    channel, W_out), an attention operator 10,485,888 (q and o of 2048
    x 2048, k and v of 2048 x 512, two head norms of 64), an expert
    9,437,184, the dense FFN 72,351,744 (ISSUE 50's counts), the router
    2048 x 64 and its bias; ONE matrix of 8,192 x 2048 for embedding
    and head; and the published model whole."""
    assert (CONV_OPERATOR, ATTN_OPERATOR, EXPERT, DENSE) == (
        16_783_360, 10_485_888, 9_437_184, 72_351_744)
    norms = 2 * 2048
    moe = 2048 * 64 + 64 + 8 * EXPERT
    held = (CONV_OPERATOR + norms + DENSE + ATTN_OPERATOR + norms + moe
            + 3 * (CONV_OPERATOR + norms + moe) + 8_192 * 2048 + 2048)
    assert held == CONF["model_sizes"]["parameters"] == 469_285_248
    whole_moe = 2048 * 64 + 64 + 64 * EXPERT
    whole = (30 * CONV_OPERATOR + 10 * ATTN_OPERATOR + 40 * norms
             + 2 * DENSE + 38 * whole_moe + 65_536 * 2048 + 2048)
    assert (whole == CONF["model_sizes"]["parameters_published"]
            == CONF["published"]["parameters"])
    assert 23e9 < whole < 25e9
    assert CONF["published"] == {
        "num_hidden_layers": 40, "num_dense_layers": 2, "num_experts": 64,
        "vocab_size": 65_536, "parameters": whole}


def test_the_two_counts_by_hand():
    """The model count: a conv operator's forward 2 x its two matrices
    + 8 x 2048 of the mixer, an attention operator's four projections
    and 4 x 64 x 32 a pair, the head AS IT RUNS (one product and four
    column reads' worth a trained token, none in the prefix); the conv
    operator's floor: every product once at the MXU's peak, 170 ns a
    token and forward pass, against 10 of bytes."""
    m, sizes = CONF["model_sizes"], CONF["sizes"]
    rest, pair, product, column = flops_lfm2.token_flops(m)
    conv = 2 * (2048 * 6144 + 2048 * 2048) + 8 * 2048
    attn = 2 * (2 * 2048 * 2048 + 2 * 2048 * 512)
    moe = 2 * 2048 * 64 + 6 * 2048 * 1536 * (4 * 8 / 64)
    assert rest == pytest.approx(4 * conv + attn + 6 * 2048 * 11776 + 4 * moe)
    assert (pair, product, column) == (4 * 64 * 32, 2 * 2048 * 8_192,
                                       2 * 2048)
    flops_lfm2.register(m)
    got = flops.TRAIN_STEP_FLOPS[CONF["family"]](sizes)
    burn, trained = 4096, 12288
    pairs_burn = burn * (burn + 1) // 2
    pairs_trained = burn * trained + trained * (trained + 1) // 2
    assert got == pytest.approx(2 * (
        2 * (burn * rest + pair * pairs_burn)
        + 4 * (trained * rest + pair * pairs_trained)
        + trained * (product + 4 * column)))
    # the dense read would count four products, and the gap is the
    # over-count PERF.md section 7 has for the two older column readers
    dense = flops_lfm2.model_step_flops(sizes, {**m, "head_by_column": False})
    assert dense - got == pytest.approx(
        2 * trained * (3 * product - 4 * column))
    work, moved = flops_lfm2.conv_work(sizes, m)
    passes = 2 * 4 * (2 * burn + 3 * trained)
    back = 2 * 4 * trained
    assert flops_lfm2.conv_flops(m) == conv
    assert work == conv * (passes + 2 * back)
    assert moved == 2 * 2048 * (passes * 2 + back * 3)
    peak = peaks_for("TPU v5 lite")
    floor = flops_lfm2.conv_floor_seconds(sizes, m, peak)
    assert floor == work / 197e12 > 10 * moved / 819e9
    assert conv / 197e12 == pytest.approx(170e-9, rel=0.01)
    assert 2 * 2048 * 2 / 819e9 == pytest.approx(10e-9, rel=0.01)
    # the projections are all but a two-thousandth of it: the floor
    # holds whatever becomes of the gates and the filter
    assert 8 * 2048 / conv < 1 / 2000
    # float32 compute doubles the bytes and nothing else
    assert flops_lfm2.conv_work({**sizes, "compute_dtype": "float32"}, m) == (
        work, 2 * moved)


def test_the_preset_is_the_published_model():
    from ape_x_dqn_tpu.configs import get_config

    lf = get_config(CONF["preset"]).network.lfm2_moe
    assert (lf.num_hidden_layers, lf.num_dense_layers, lf.num_experts,
            lf.vocab_size, lf.shard_count) == (40, 2, 64, 65_536, 1)
    assert list(lf.layer_types) == CONF["layer_types"]
    assert not lf.force_balanced_routing
    assert _cfg().network.lfm2_moe.force_balanced_routing
    assert "force_balanced_routing" in CONF["assumed"]["routing"]


def test_the_file_holds_the_catalog_rows_keys():
    """Every key of the catalog row's `config`, under the same name, at
    the same value - but the four `reduced` names, which give what is
    held here. `layer_types` is the published list, whole; the five
    layers held are `model_sizes.layer_types`, the published layers
    1-5."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "LFM2-24B-A2B")
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
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "conv_L_cache",
                "conv_bias", "num_experts", "num_experts_per_tok",
                "norm_topk_prob", "routed_scaling_factor", "use_expert_bias",
                "norm_eps", "vocab_size", "max_position_embeddings"):
        assert m[key] == row["config"][key], key
    assert m["rope_theta"] == row["config"]["rope_parameters"]["rope_theta"]
    assert m["layer_types"] == row["config"]["layer_types"][1:6]
    assert row["head_dim"] is None
    # every (+) of the issue is stated
    assert sum(k.startswith("(+) ") for k in CONF["assumed"]) == 9


def test_overrides_are_the_reduced_keys_and_the_share():
    keys = [o.split("=")[0] for o in CONF["overrides"]]
    assert keys == ["network.lfm2_moe.num_hidden_layers",
                    "network.lfm2_moe.num_dense_layers",
                    "network.lfm2_moe.layer_types",
                    "network.lfm2_moe.shard_count",
                    "env.num_tokens", "actors.num_actors",
                    "eval_every_steps", "eval_episodes",
                    "network.lfm2_moe.force_balanced_routing"]


def test_the_cell_and_its_metrics_are_declared():
    """PRESENCE only: no assertion on WHERE in the lists the entries
    stand, nor that a shared metric lists this cell alone - a later PR
    appends, and five such assertions in the older cells' tests went
    red for it (PERF.md section 7)."""
    bench = cells.load_benchmark()
    cell = cells.resolve(CELL)
    assert (cell.chips, cell.config_name) == (1, "lfm2_24b_ep8_1chip")
    assert cell.traffic["kind"] == "conv_token_sequence_free_run"
    assert {m["name"] for m in cell.end_to_end} == {
        "learn_samples_per_s", "peak_hbm_gib", "setup_s"}
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, better, source, layer) in NEW_METRICS.items():
        m = declared[name]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (unit, better, source, layer,
                                "learn_samples_per_s")
        assert CELL in m["workloads"]
        assert callable(cells.layer_metric_reader(name).read)
    reported = {m["name"] for m in cell.per_layer}
    assert reported >= set(NEW_METRICS) | {
        "learner.step_ms", "learner.mfu", "kernels.mxu_share",
        "device.idle_share", "learner.burn_in_share", "learner.moe_share",
        "moe.load_max_over_mean", "moe.compact_share",
        "kernels.moe_expert_mm_roofline", "learner.loss_grad_share",
        "learner.optimizer_share", "learner.health_share",
        "learner.cycle_unscoped_share", "learner.attn_share",
        "learner.attn_full_share"}
    # the attention's count takes every entry of `layer_types` for a
    # layer with pairs, and the dense FFN's readers read Ouro's scope
    assert not reported & {"kernels.attn_flash_roofline",
                           "learner.dense_ffn_share",
                           "kernels.dense_ffn_mm_roofline"}
    mix = cell.traffic
    assert (mix["ring_fill"], mix["fill_sequences_per_add"],
            mix["token_zipf_exponent"], mix["priority_lognormal_sigma"],
            mix["terminal_one_in"], mix["episode_tail_one_in"],
            mix["reward_one_in"], mix["max_dispatches_in_flight"],
            mix["trace_window_s"]) == (
        1.0, 8, 1.0, 1.0, 8 * 16_384, 16, 64, 2, 4.0)


def test_the_new_readers_return_nothing_where_there_is_nothing():
    """What the parent's program gives them: no counter, no scope."""
    positions = cells.layer_metric_reader("conv.positions_mixed")
    assert positions.read({}) is None and positions.read({"conv": {}}) is None
    assert positions.read({"conv": {"positions_mixed": 131072.0}}) == 131072.0

    class _Runtime:
        cell = cells.resolve(CELL)
        devices = [type("D", (), {"device_kind": "TPU v5 lite"})()]

        @staticmethod
        def newest_xplane():
            return None

    def facts(table):
        return {"lfm2_scope_ns": dict(table), "runtime": _Runtime,
                "batch_size": 2, "train_chunk": 2,
                "trace": {"devices": [{
                    "busy_ns": 1000,
                    "modules": {"jit_train_many": {"median_ns": 1.6e9}}}]}}

    share = cells.layer_metric_reader("learner.conv_share")
    mix = cells.layer_metric_reader("learner.conv_mix_share")
    roof = cells.layer_metric_reader("kernels.short_conv_roofline")
    for reader in (share, mix, roof):
        assert reader.read(facts({})) is None
    full = facts({"lfm2.conv": 250, "lfm2.conv.mix": 125})
    assert share.read(full) == 25.0 and mix.read(full) == 12.5
    # a step of 0.8 s, a quarter of it under `lfm2.conv`: 0.2 s
    floor = flops_lfm2.conv_floor_seconds(
        CONF["sizes"], CONF["model_sizes"], peaks_for("TPU v5 lite"))
    assert roof.read(full) == pytest.approx(100.0 * floor / 0.2)
    assert 0.0 < roof.read(full) < 100.0
    # the share is the whole operator's: what moves between the scopes
    # inside `lfm2.conv` (a gate fused into a projection) moves nothing
    assert roof.read(facts({"lfm2.conv": 250})) == roof.read(full)
    assert roof.read(facts({"lfm2.conv.mix": 250})) is None
    # a configuration of another family leaves nothing to count
    other = facts({"lfm2.conv": 250})
    other["runtime"] = type("R", (), {
        "cell": cells.resolve("glm47_flash_offline"),
        "devices": _Runtime.devices})
    assert roof.read(other) is None


# -- the kind end to end on the CPU ------------------------------------------

# the tiny preset's widths (hidden 32, 4 query heads to 2 key-value
# heads of 12, 8 experts top-2 of which 4 are held, 32 of 64 vocabulary
# rows, conv + dense, attention + experts, conv + experts, 32-token
# sequences with a prefix of 12)
TINY = ("network.lfm2_moe.hidden_size=32",
        "network.lfm2_moe.intermediate_size=64",
        "network.lfm2_moe.moe_intermediate_size=16",
        "network.lfm2_moe.layer_types=('conv','full_attention','conv')",
        "network.lfm2_moe.num_hidden_layers=3",
        "network.lfm2_moe.num_attention_heads=4",
        "network.lfm2_moe.num_key_value_heads=2",
        "network.lfm2_moe.head_dim=12",
        # the share first: each override is checked as it is set
        "network.lfm2_moe.shard_count=2",
        "network.lfm2_moe.num_experts=8",
        "network.lfm2_moe.num_experts_per_tok=2",
        "network.lfm2_moe.vocab_size=64", "env.num_tokens=32",
        "learner.batch_size=4", "replay.capacity=64",
        "replay.seq_length=32", "replay.burn_in=12",
        "replay.seq_overlap=16", "learner.n_step=2")
TRAFFIC = {"fill_sequences_per_add": 16, "episode_tail_one_in": 4,
           "terminal_one_in": 16, "reward_one_in": 4}
DEPARTURES = ("conv_tail_ignored", "conv_out_gate_left_out",
              "conv_silu_added", "qk_norm_left_out", "head_untied")


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

    from ape_x_dqn_tpu.models import expert_layer
    from benchmarks.harness import token_sequence_checks as limits

    jax.config.update("jax_enable_compilation_cache", False)
    # as test_run_afmoe_cpu.py: at these widths a norm gain is a leaf of
    # 32 values and ratios of two such norms swing
    monkeypatch.setattr(limits, "GRAD_RATIO", 6.0)
    monkeypatch.setattr(limits, "GRAD_MEDIAN_RATIO", 2.0)
    # every matrix and filter normal(0, 1 / sqrt(32)) where the cell's
    # are normal(0, 0.02) at hidden 2,048: a projection's output of
    # order 1 at this width too, or a conv operator (three projections
    # and a filter deep) is a ten-thousandth of the stream and no
    # departure inside it is seen
    monkeypatch.setattr(expert_layer, "INIT_STD", 32 ** -0.5)
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
    assert "'window_ignored'" not in said
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
        "q_behind_the_prefix_matches_reference",
        "gradients_match_reference",
        "routing_matches_reference_outside_margin",
        "moe_rows_counter_matches_selection",
        "conv_positions_counter_matches_the_shapes", "tree_root_is_leaf_sum",
        "valid_frac_is_the_seeded_share", "every_loss_finite",
        "step_counter_closes"}
    assert facts["batch_size"] == 4 and facts["train_chunk"] == 2
    assert facts["fill"]["transitions"] == 64 * 32     # tokens stored
    # two conv layers x 32 positions x 4 sequences
    assert facts["conv"]["positions_mixed"] == 2 * 32 * 4
    moe = facts["moe"]
    assert 0 < moe["rows_grad_per_step"] < moe["rows_per_step"]
    assert facts["family"] == "lfm2_moe_q"
    assert facts["family"] in flops.TRAIN_STEP_FLOPS


@pytest.mark.parametrize("departure", DEPARTURES + ("one_bit_less",))
def test_a_departure_in_the_program_turns_correct_false(monkeypatch,
                                                        departure):
    """The same departures, and a mantissa one bit short, made in the
    PROGRAM: the run as the cell makes it (no `show_limits`) comes out
    not correct."""
    import jax
    import jax.numpy as jnp

    from ape_x_dqn_tpu.models import lfm2_moe_q, ouro_q

    if departure == "conv_tail_ignored":
        real = lfm2_moe_q.behind
        monkeypatch.setattr(lfm2_moe_q, "behind",
                            lambda tail, x, taps: real(None, x, taps))
    elif departure == "conv_out_gate_left_out":
        monkeypatch.setattr(lfm2_moe_q, "_out_gate",
                            lambda c32, mixed32: mixed32)
    elif departure == "conv_silu_added":
        real = lfm2_moe_q.short_conv
        monkeypatch.setattr(
            lfm2_moe_q, "short_conv",
            lambda seen, w, t: jax.nn.silu(real(seen, w, t)))
    elif departure == "qk_norm_left_out":
        monkeypatch.setattr(lfm2_moe_q, "_head_norm", lambda x, g, eps: x)
    elif departure == "head_untied":
        def second_matrix(self, params):
            e = params["embed_tokens"]
            return 32 ** -0.5 * jax.random.normal(
                jax.random.key(9), e.shape, e.dtype)

        monkeypatch.setattr(lfm2_moe_q.Lfm2MoeQNet, "_head_rows",
                            second_matrix)
    else:
        def six_bits(x32, dt):
            if dt == jnp.float32:
                return x32
            info = jnp.finfo(dt)
            return jax.lax.reduce_precision(
                x32, info.nexp, info.nmant - 1).astype(dt)

        monkeypatch.setattr(ouro_q, "_held", six_bits)
        monkeypatch.setattr(lfm2_moe_q, "_held", six_bits)
    result, facts = _tiny_run(monkeypatch)
    assert not result["correct"], departure
    if departure == "conv_tail_ignored":
        # the rule that holds the two rows at the cell's size, where
        # two trained positions of 12,288 move no percentile over all
        assert not facts["checks"]["q_behind_the_prefix_matches_reference"]
    assert facts["checks"]["every_loss_finite"]
    assert facts["checks"]["conv_positions_counter_matches_the_shapes"]
