"""configs/kimi_linear_48b_ep32_1chip.json against the preset and against
the catalog row it was drawn from, the parameter count from shapes, the
family's two counts by hand, the key the accepted expert-matmul reader
takes held to the model's own, the new metrics' declarations - and the
traffic kind `kda_token_sequence_free_run` end to end at the tiny
preset's widths on the CPU, through `runner.run_cell`, `correct` true
as the cell runs and false under each departure the check must refuse
and under one bit less, made in the PROGRAM."""

import dataclasses
import json
import os
import time

import pytest

from benchmarks.harness import (cells, flops, flops_glm_moe,
                                flops_kimi_linear, runner)
from benchmarks.harness.peaks import peaks_for

CELL = "kimi_linear_offline"
CONF = cells.resolve(CELL).config
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": (27, 5), "num_experts": (256, 8),
           "vocab_size": (163_840, 20_480)}
NEW_METRICS = {
    "learner.kda_share": ("%", "lower", "device_trace", "learner"),
    "learner.kda_scan_share": ("%", "lower", "device_trace", "learner"),
    "kda.chunks_walked": ("chunks", "higher", "program_counter", "learner"),
    "kernels.kda_scan_roofline": ("%", "higher", "device_trace",
                                  "kernels (XLA)")}
KDA_MIXER = (4 * 2304 * 4096 + 3 * 4 * 4096 + 32 + 4096
             + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32 + 128)
MLA_MIXER = (2304 * 32 * 192 + 2304 * 576 + 512 + 512 * 32 * 256
             + 32 * 128 * 2304)
EXPERT = 3 * 2304 * 1024


def _cfg():
    from ape_x_dqn_tpu.configs import get_config
    from ape_x_dqn_tpu.runtime.train import apply_overrides

    return apply_overrides(get_config(CONF["preset"]), CONF["overrides"])


def test_model_sizes_are_what_preset_plus_overrides_build():
    cfg = _cfg()
    kl, m = cfg.network.kimi_linear, CONF["model_sizes"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_hidden_layers", "first_k_dense_replace",
                "linear_num_heads", "linear_head_dim",
                "linear_short_conv_kernel_size", "num_attention_heads",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "mla_use_nope", "num_experts",
                "num_shared_experts", "num_experts_per_token",
                "num_expert_group", "topk_group", "moe_renormalize",
                "routed_scaling_factor", "vocab_size", "rms_norm_eps",
                "shard_count", "shard_index", "vocab_shard_count",
                "force_balanced_routing"):
        assert m[key] == getattr(kl, key), key
    assert m["full_attn_layers"] == list(kl.full_attn_layers)
    from ape_x_dqn_tpu.models import build_network
    from ape_x_dqn_tpu.ops.chunked_delta_rule import CHUNK

    net = build_network(cfg.network, None)
    assert m["mixer_types"] == list(net.layer_kinds) == [
        "kda", "kda", "kda", "mla", "kda"]
    assert m["kda_chunk"] == net.kda_chunk == CHUNK
    assert m["experts_held"] == net.experts_held == 8
    assert m["vocab_held"] == net.num_actions == CONF["sizes"]["num_actions"]
    assert m["parameters"] == net.param_count() == 602_434_432
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
    assert cfg.network.kind == "kimi_linear_q"
    assert cfg.replay.kind == "sequence"
    assert CONF["family"] == flops_kimi_linear.FAMILY
    assert CONF["layout"]["layer_shared_by"] == kl.shard_count == 32
    assert CONF["layout"]["vocabulary_shared_by"] == kl.vocab_shard_count == 8
    assert cfg.env.num_tokens == net.num_actions
    assert cfg.replay.capacity == 8192 and cfg.learner.batch_size == 1
    # the sequence was taken by ISSUE 46's rule from the compiled memory
    mem = CONF["memory"]
    assert mem["of"] - mem["sequence_8192_burn_2048"]["total"] < 0.75
    assert mem["of"] - mem["sequence_4096_burn_1024"]["total"] >= 0.75
    for cut in ("sequence_8192_burn_2048", "sequence_4096_burn_1024"):
        assert mem[cut]["parameters"] == 602_434_432


def test_the_key_the_accepted_reader_takes_repeats_the_models_own():
    """`kernels.moe_expert_mm_roofline` reads GLM's key names: widths
    2304 x 1024 under the keys it reads."""
    m = CONF["model_sizes"]
    assert m["num_experts_per_tok"] == m["num_experts_per_token"] == 8
    assert flops_glm_moe.executed_expert_flops(100.0, 10.0, m) == \
        6 * 2304 * 1024 * 130.0


def test_the_parameter_count_by_hand():
    """A KDA mixer 39,514,272 (four projections of 2304 x 4096, three
    filters of 4 x 4096, A_log, dt_bias, two low-rank gates of 2304 x
    128 x 4096, beta's 2304 x 32, the output norm), an MLA mixer
    29,114,880, an expert 7,077,888 (ISSUE 46's three counts), the
    router 2304 x 256 and its bias; embedding + head 2 x 20,480 x 2304;
    and the published model whole."""
    assert (KDA_MIXER, MLA_MIXER, EXPERT) == (39_514_272, 29_114_880,
                                              7_077_888)
    norms = 2 * 2304
    moe = 2304 * 256 + 256 + 9 * EXPERT
    dense = 3 * 2304 * 9216
    held = (KDA_MIXER + norms + dense + 3 * (KDA_MIXER + norms + moe)
            + MLA_MIXER + norms + moe + 2 * 20_480 * 2304 + 2304)
    assert held == CONF["model_sizes"]["parameters"] == 602_434_432
    whole_moe = 2304 * 256 + 256 + 257 * EXPERT
    whole = (20 * KDA_MIXER + 7 * MLA_MIXER + 27 * norms + dense
             + 26 * whole_moe + 2 * 163_840 * 2304 + 2304)
    assert (whole == CONF["model_sizes"]["parameters_published"]
            == CONF["published"]["parameters"])
    assert 48e9 < whole < 50e9
    assert CONF["published"] == {
        "num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163_840,
        "parameters": whole}


def test_the_two_counts_by_hand():
    """The model count: a KDA mixer's forward 2 x its matrices + the
    convolutions + 7 x 128 x 128 x 32 of the rule, an MLA mixer's four
    projections and 640 x 32 a pair; the scan's floor: bytes, 3.1 ns a
    token, head and forward pass."""
    m = CONF["model_sizes"]
    rest, pair, head = flops_kimi_linear.token_flops(m)
    kda = (2 * (4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32)
           + 2 * 3 * 4 * 4096 + 7 * 128 * 128 * 32)
    mla = 2 * (2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256
               + 32 * 128 * 2304)
    moe = 2 * 2304 * 256 + 6 * 2304 * 1024 * (1 + 8 * 8 / 256)
    assert rest == pytest.approx(4 * kda + mla + 6 * 2304 * 9216 + 4 * moe)
    assert (pair, head) == (640 * 32, 2 * 2304 * 20_480)
    flops_kimi_linear.register(m)
    got = flops.TRAIN_STEP_FLOPS[CONF["family"]](CONF["sizes"])
    burn, trained = 1024, 3072
    pairs_burn = burn * (burn + 1) // 2
    pairs_trained = burn * trained + trained * (trained + 1) // 2
    assert got == pytest.approx(
        2 * (burn * (rest + head) + pair * pairs_burn)
        + 4 * (trained * (rest + head) + pair * pairs_trained))
    work, moved = flops_kimi_linear.scan_work(1, m)
    passes = 32 * 4 * (2 * burn + 3 * trained)
    back = 32 * 4 * trained
    assert work == 7 * 128 * 128 * (passes + 2 * back)
    assert moved == passes * 4 * 641 + back * 4 * (513 + 128 + 513)
    peak = peaks_for("TPU v5 lite")
    floor = flops_kimi_linear.scan_floor_seconds(1, m, peak)
    assert floor == moved / 819e9 > work / 197e12
    # the chunk size is in no count
    assert flops_kimi_linear.scan_work(1, {**m, "kda_chunk": 8}) == (
        work, moved)


def test_the_preset_is_the_published_model():
    from ape_x_dqn_tpu.configs import get_config

    kl = get_config(CONF["preset"]).network.kimi_linear
    assert (kl.num_hidden_layers, kl.num_experts, kl.vocab_size,
            kl.shard_count) == (27, 256, 163_840, 1)
    assert list(kl.full_attn_layers) == \
        CONF["linear_attn_config"]["full_attn_layers"]
    every = set(range(1, 28))
    assert every - set(kl.full_attn_layers) == set(
        CONF["linear_attn_config"]["kda_layers"])
    assert not kl.force_balanced_routing
    assert _cfg().network.kimi_linear.force_balanced_routing
    assert "force_balanced_routing" in CONF["assumed"]["routing"]


def test_the_file_holds_the_catalog_rows_keys():
    """Every key of the catalog row's `config`, under the same name, at
    the same value - but the three `reduced` names, which give what is
    held here. `linear_attn_config` is the published group, whole; the
    five layers held are `model_sizes.mixer_types`."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
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
                "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "num_experts",
                "num_experts_per_token", "num_shared_experts",
                "routed_scaling_factor", "rms_norm_eps", "vocab_size",
                "first_k_dense_replace", "mla_use_nope"):
        assert m[key] == row["config"][key], key
    group = row["config"]["linear_attn_config"]
    assert (m["linear_num_heads"], m["linear_head_dim"],
            m["linear_short_conv_kernel_size"]) == (
        group["num_heads"], group["head_dim"],
        group["short_conv_kernel_size"])
    assert m["mixer_types"] == [
        "mla" if l in group["full_attn_layers"] else "kda"
        for l in range(1, 6)]
    assert row["config"]["q_lora_rank"] is None
    assert row["config"]["num_nextn_predict_layers"] == 0
    # every (+) of the issue is stated
    assert sum(k.startswith("(+) ") for k in CONF["assumed"]) == 7


def test_overrides_are_the_reduced_keys_and_the_share():
    keys = [o.split("=")[0] for o in CONF["overrides"]]
    assert keys == ["network.kimi_linear.num_hidden_layers",
                    "network.kimi_linear.shard_count",
                    "network.kimi_linear.vocab_shard_count",
                    "env.num_tokens", "actors.num_actors",
                    "eval_every_steps", "eval_episodes",
                    "network.kimi_linear.force_balanced_routing"]


def test_the_cell_and_its_metrics_are_declared():
    bench = cells.load_benchmark()
    cell = cells.resolve(CELL)
    assert (cell.chips, cell.config_name) == (1, "kimi_linear_48b_ep32_1chip")
    assert cell.traffic["kind"] == "kda_token_sequence_free_run"
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
        "device.idle_share", "learner.burn_in_share", "learner.moe_share",
        "learner.mla_share", "moe.load_max_over_mean", "moe.compact_share",
        "kernels.moe_expert_mm_roofline", "learner.loss_grad_share",
        "learner.optimizer_share", "learner.health_share",
        "learner.cycle_unscoped_share", "setup.compile_s",
        "replay.fill_transitions_per_s"}
    # the attention readers' scopes (`afmoe.attn*`) are not this net's
    assert "kernels.attn_flash_roofline" not in reported
    mix = cell.traffic
    assert (mix["ring_fill"], mix["token_zipf_exponent"],
            mix["priority_lognormal_sigma"], mix["terminal_one_in"],
            mix["episode_tail_one_in"], mix["reward_one_in"],
            mix["max_dispatches_in_flight"], mix["trace_window_s"]) == (
        1.0, 1.0, 1.0, 8 * 4096, 16, 64, 2, 4.0)
    # (no assertion on WHERE in the lists the entries stand, nor that a
    # shared metric lists this cell alone: a later PR appends, and such
    # assertions in the older cells' tests went red for it - PERF.md
    # section 7)
    assert len(bench["workloads"]) >= 9 and len(bench["configs"]) >= 8


def test_the_new_readers_return_nothing_where_there_is_nothing():
    """What the parent's program gives them: no counter, no scope."""
    chunks = cells.layer_metric_reader("kda.chunks_walked")
    assert chunks.read({}) is None and chunks.read({"kda": {}}) is None
    assert chunks.read({"kda": {"chunks_walked": 256.0}}) == 256.0

    class _Runtime:
        cell = cells.resolve(CELL)
        devices = [type("D", (), {"device_kind": "TPU v5 lite"})()]

        @staticmethod
        def newest_xplane():
            return None

    def facts(table):
        return {"kda_scope_ns": dict(table), "runtime": _Runtime,
                "batch_size": 1, "train_chunk": 2,
                "trace": {"devices": [{
                    "busy_ns": 1000,
                    "modules": {"jit_train_many": {"median_ns": 8e8}}}]}}

    share = cells.layer_metric_reader("learner.kda_share")
    scan = cells.layer_metric_reader("learner.kda_scan_share")
    roof = cells.layer_metric_reader("kernels.kda_scan_roofline")
    for reader in (share, scan, roof):
        assert reader.read(facts({})) is None
    full = facts({"kda": 500, "kda.scan": 250})
    assert share.read(full) == 50.0 and scan.read(full) == 25.0
    # a step of 0.4 s, a quarter of it under the scope: 0.1 s
    m = CONF["model_sizes"]
    floor = flops_kimi_linear.scan_floor_seconds(
        1, m, peaks_for("TPU v5 lite"))
    assert roof.read(full) == pytest.approx(100.0 * floor / 0.1)
    assert 0.0 < roof.read(full) < 100.0
    # a configuration of another family leaves nothing to count
    other = facts({"kda.scan": 250})
    other["runtime"] = type("R", (), {
        "cell": cells.resolve("glm47_flash_offline"),
        "devices": _Runtime.devices})
    assert roof.read(other) is None


# -- the kind end to end on the CPU ------------------------------------------

# the tiny preset's widths (hidden 48, 3 KDA heads of 8, 2 MLA heads of
# 12 + 4 over values of 8, 8 experts top-2 of which 4 are held, 32 of 64
# vocabulary rows, KDA + dense, KDA, MLA, KDA over experts, 32-token
# sequences with a prefix of 12)
TINY = ("network.kimi_linear.hidden_size=48",
        "network.kimi_linear.intermediate_size=96",
        "network.kimi_linear.moe_intermediate_size=24",
        "network.kimi_linear.num_hidden_layers=4",
        "network.kimi_linear.full_attn_layers=(3,)",
        "network.kimi_linear.linear_num_heads=3",
        "network.kimi_linear.linear_head_dim=8",
        "network.kimi_linear.num_attention_heads=2",
        "network.kimi_linear.kv_lora_rank=16",
        "network.kimi_linear.qk_nope_head_dim=12",
        "network.kimi_linear.qk_rope_head_dim=4",
        "network.kimi_linear.v_head_dim=8",
        # the share first: each override is checked as it is set
        "network.kimi_linear.shard_count=2",
        "network.kimi_linear.vocab_shard_count=2",
        "network.kimi_linear.num_experts=8",
        "network.kimi_linear.num_experts_per_token=2",
        "network.kimi_linear.vocab_size=64", "env.num_tokens=32",
        "learner.batch_size=4", "replay.capacity=64",
        "replay.seq_length=32", "replay.burn_in=12",
        "replay.seq_overlap=16", "learner.n_step=2")
TRAFFIC = {"fill_sequences_per_add": 16, "episode_tail_one_in": 4,
           "terminal_one_in": 16, "reward_one_in": 4}
DEPARTURES = ("decay_per_head", "short_conv_left_out", "mla_rotated",
              "gate_silu_for_sigmoid")


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

    from ape_x_dqn_tpu.models import kimi_linear_q
    from benchmarks.harness import token_sequence_checks as limits

    jax.config.update("jax_enable_compilation_cache", False)
    # as test_run_afmoe_cpu.py: at these widths a norm gain is a leaf of
    # 48 values and ratios of two such norms swing
    monkeypatch.setattr(limits, "GRAD_RATIO", 6.0)
    monkeypatch.setattr(limits, "GRAD_MEDIAN_RATIO", 2.0)
    # chunks of 8, so that a 32-token sequence walks several
    monkeypatch.setattr(kimi_linear_q, "CHUNK", 8)
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
        "gradients_match_reference",
        "routing_matches_reference_outside_margin",
        "moe_rows_counter_matches_selection",
        "kda_chunks_counter_matches_the_shapes", "tree_root_is_leaf_sum",
        "valid_frac_is_the_seeded_share", "every_loss_finite",
        "step_counter_closes"}
    assert facts["batch_size"] == 4 and facts["train_chunk"] == 2
    assert facts["fill"]["transitions"] == 64 * 32     # tokens stored
    # three KDA layers x (2 chunks of the prefix + 3 of the trained steps)
    assert facts["kda"]["chunks_walked"] == 3 * (2 + 3)
    assert 0.0 < facts["kda"]["state_rms_last"] < 1.0
    moe = facts["moe"]
    assert 0 < moe["rows_grad_per_step"] < moe["rows_per_step"]
    assert facts["family"] == "kimi_linear_kda_q"
    assert facts["family"] in flops.TRAIN_STEP_FLOPS


class _Over:
    """`real` with some attributes replaced."""

    def __init__(self, real, **over):
        self._real, self._over = real, over

    def __getattr__(self, name):
        over = self.__dict__["_over"]
        return over[name] if name in over else getattr(self._real, name)


@pytest.mark.parametrize("departure", DEPARTURES + ("one_bit_less",))
def test_a_departure_in_the_program_turns_correct_false(monkeypatch,
                                                        departure):
    """The same departures, and a mantissa one bit short, made in the
    PROGRAM: the run as the cell makes it (no `show_limits`) comes out
    not correct."""
    import jax
    import jax.numpy as jnp

    from ape_x_dqn_tpu.models import kimi_linear_q, ouro_q

    if departure == "decay_per_head":
        real = kimi_linear_q.chunked_delta_rule

        def one_decay_a_head(q, k, v, g, beta, state, **kw):
            g = jnp.broadcast_to(g.mean(axis=-1, keepdims=True), g.shape)
            return real(q, k, v, g, beta, state, **kw)

        monkeypatch.setattr(kimi_linear_q, "chunked_delta_rule",
                            one_decay_a_head)
    elif departure == "short_conv_left_out":
        monkeypatch.setattr(
            kimi_linear_q, "_short_conv",
            lambda seen, w, t: seen[:, seen.shape[1] - t:].astype(
                jnp.float32))
    elif departure == "mla_rotated":
        real = kimi_linear_q.mla_module

        def rotated(p, x, cache, dt, m, blocks=None):
            return real.mla(p, x, cache, dt,
                            m._replace(rope_theta=10_000.0), blocks)

        monkeypatch.setattr(kimi_linear_q, "mla_module",
                            _Over(real, mla=rotated))
    elif departure == "gate_silu_for_sigmoid":
        monkeypatch.setattr(kimi_linear_q, "_output_gate", jax.nn.silu)
    else:
        def six_bits(x32, dt):
            if dt == jnp.float32:
                return x32
            info = jnp.finfo(dt)
            return jax.lax.reduce_precision(
                x32, info.nexp, info.nmant - 1).astype(dt)

        monkeypatch.setattr(ouro_q, "_held", six_bits)
        monkeypatch.setattr(kimi_linear_q, "_held", six_bits)
    result, facts = _tiny_run(monkeypatch)
    assert not result["correct"], departure
    assert facts["checks"]["every_loss_finite"]
    assert facts["checks"]["kda_chunks_counter_matches_the_shapes"]
