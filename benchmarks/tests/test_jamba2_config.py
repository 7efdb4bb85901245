"""The configuration `jamba2_3b_1chip`, the mix `decode_sessions_wide`
and the cell `jamba2_decode_wide` (PR 57): the file's sizes are what the
preset plus the overrides build and hold the catalog row's keys, nothing
reduced but run length; the counts of a 128-row decode step by hand;
the cell and its metrics are declared; every `checks` module imports;
every new reader returns nothing on facts without its inputs; and the
kind runs end to end at tiny widths on the CPU, through
`runner.run_cell`, `correct` true as the cell runs it and every reading
that has to fail failing under `show_limits`."""

import dataclasses
import importlib
import json
import os
import time

import pytest

from benchmarks.harness import cells, runner
from benchmarks.harness import flops_jamba as counts

CELL = "jamba2_decode_wide"
CONF = cells.resolve(CELL).config
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {
    "server.ssm_step_mfu": ("%", "higher", "host_clock", "inference",
                            "infer_p99_ms"),
    "server.ssm_step_hbm_roofline": ("%", "higher", "device_trace",
                                     "inference", "infer_p99_ms"),
    "serve.mamba_share": ("%", "lower", "device_trace", "inference",
                          "infer_p99_ms"),
    "serve.ssm_scan_share": ("%", "lower", "device_trace", "inference",
                             "infer_p99_ms"),
    "kernels.ssm_state_roofline": ("%", "higher", "device_trace",
                                   "kernels (XLA)", "infer_p99_ms"),
    "ssm.rows_updated_per_step": ("rows", "higher", "program_counter",
                                  "inference", "infer_p99_ms"),
    "serve.prefill_tokens_per_s": ("tokens/s", "higher", "host_clock",
                                   "inference", "setup_s")}
SHARED = ("server.p50_ms", "server.queue_wait_ms", "driver.gc_pause_ms",
          "server.decode_step_ms", "device.idle_share_serve")
# the tiny preset's widths on the cell's preset, and a server to match
TINY = (
    "network.jamba.hidden_size=64", "network.jamba.num_hidden_layers=6",
    "network.jamba.attn_layer_offset=1", "network.jamba.attn_layer_period=3",
    "network.jamba.num_attention_heads=4",
    "network.jamba.intermediate_size=96", "network.jamba.mamba_d_state=4",
    "network.jamba.mamba_dt_rank=8", "network.jamba.vocab_size=64",
    "env.num_tokens=64", "inference.slots=4", "inference.slot_max_len=4072",
    "inference.slot_pool_tokens=16384", "inference.prefill_chunk=16",
    "inference.prefill_rows=2", "inference.max_batch=4")
# contexts of hundreds of positions: a carry rounded to bfloat16 reads
# 3 of the state rule's units after 300 positions and 7 after 1,100
TRAFFIC = {"clients": 2, "sessions_per_client": 2, "start_min": 600,
           "start_max": 800, "decode_max": 3200, "settle_s": 0.2,
           "checked_steps": 4}


def _cfg():
    from ape_x_dqn_tpu.configs import get_config
    from ape_x_dqn_tpu.runtime.train import apply_overrides

    return apply_overrides(get_config(CONF["preset"]), CONF["overrides"])


def test_model_sizes_are_what_preset_plus_overrides_build():
    from ape_x_dqn_tpu.configs import get_config
    from ape_x_dqn_tpu.models import build_network
    from ape_x_dqn_tpu.runtime import family as fam

    cfg = _cfg()
    m = CONF["model_sizes"]
    for key, value in dataclasses.asdict(cfg.network.jamba).items():
        assert m[key] == value, key
    net = build_network(cfg.network, None)
    assert m["layer_kinds"] == list(net.kinds)
    assert [i for i, k in enumerate(net.kinds) if k == "attention"] == [7, 21]
    assert (m["head_dim"], m["d_inner"]) == (net.head_dim, net.d_inner) == (
        128, 5120)
    assert net.param_count() == m["parameters"] == 3_029_337_472
    assert CONF["sizes"]["num_actions"] == net.num_actions == 65_536
    assert cfg.network.compute_dtype == CONF["sizes"]["compute_dtype"]
    # the server's settings are THE PRESET'S (ISSUE 57): no override of any
    inf, s, preset = cfg.inference, CONF["sizes"], get_config(
        CONF["preset"]).inference
    assert not [o for o in CONF["overrides"] if o.startswith("inference.")]
    assert inf == preset
    assert (inf.max_batch, inf.deadline_ms) == (
        s["inference_max_batch"], s["inference_deadline_ms"]) == (128, 2.0)
    assert CONF["server_sizes"] == {
        "slots": 256, "slot_max_len": 10_240, "slot_pool_tokens": 2_097_152,
        "prefill_chunk": 2_048, "prefill_rows": 8} == {
        k: getattr(inf, k) for k in CONF["server_sizes"]}
    # nothing is reduced but the run's length
    assert CONF["reduced"] == ["actors.num_actors", "eval_every_steps",
                               "eval_episodes", "total_env_frames"]
    # the mix's sessions fit the server the file builds
    mix = cells.resolve(CELL).traffic
    assert mix["clients"] * mix["sessions_per_client"] == inf.slots == 256
    assert mix["sessions_per_client"] * 16 == inf.max_batch
    assert mix["start_max"] + mix["decode_max"] == inf.slot_max_len
    assert fam.slot_geometry(cfg, net.slot_block) == (
        256, 10_240, 2_097_152)
    # 5.64 GiB of bfloat16 parameters; 8.89 MiB a session at any context,
    # 1 KiB a position
    assert round(2 * m["parameters"] / 2 ** 30, 2) == 5.64
    assert counts.session_state_bytes(m) == 358_400
    assert net.slot_state_bytes(256, 2_097_152, 10_240) == (
        257 * (26 * 358_400 + 4) + (2_097_152 + 10_240) * 1_024)


def test_the_file_holds_the_catalog_rows_keys():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "AI21-Jamba2-3B")
    assert CONF["source"].startswith(row["source_url"])
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == CONF["name"])
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == CONF["reduced"]
    assert set(CONF["reduced_why"]) == set(CONF["reduced"])
    # every key of the row as published, at the top level and again
    # under `published`
    for key, published in row["config"].items():
        assert CONF[key] == published == CONF["published"][key], key
    m = CONF["model_sizes"]
    for key in ("hidden_size", "intermediate_size", "num_hidden_layers",
                "num_attention_heads", "num_key_value_heads", "vocab_size",
                "attn_layer_offset", "attn_layer_period", "mamba_d_conv",
                "mamba_d_state", "mamba_dt_rank", "mamba_expand",
                "mamba_conv_bias", "mamba_proj_bias", "num_experts",
                "max_position_embeddings", "rms_norm_eps"):
        assert m[key] == row["config"][key], key
    assert CONF["published"]["parameters"] == 3_029_337_472
    assert sum(k.startswith("(+) ") for k in CONF["assumed"]) == 5
    assert {"decode_128_rows", "prefill_8_rows_x_2048", "of"} <= set(
        CONF["memory"])


def test_the_counts_of_a_decode_step_by_hand():
    """ISSUE 57's arithmetic at 128 rows of 4,500 positions."""
    m = CONF["model_sizes"]
    mamba = 2560 * 10_240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    attention = 2560 * (2560 + 256) + 2560 * 2560
    mlp = 3 * 2560 * 8192
    assert counts.matrix_params(m) == (
        26 * mamba + 2 * attention + 28 * mlp + 2560 * 65_536)
    contexts = [4_500.0] * 128
    assert counts.ssm_state_bytes(m, 128) == 128 * 26 * 358_400 * 2
    assert counts.attention_bytes(m, contexts) == 128 * 4_500 * 1_024
    assert round(2 * counts.matrix_params(m) / 1e9, 2) == 6.05
    assert round(counts.step_bytes(m, contexts) / 1e9, 1) == 9.0
    assert round(counts.step_bytes(m, contexts) / 819e9 * 1e3, 1) == 11.0
    per_row = 2 * counts.matrix_params(m) + 26 * 5120 * (2 * 4 + 6 * 16)
    assert counts.step_flops(m, [4_500.0]) == (
        per_row + 2 * 20 * 128 * 4 * 4_500)


def test_the_cell_and_its_metrics_are_declared():
    bench = cells.load_benchmark()
    assert [c["name"] for c in bench["configs"]].count(CONF["name"]) == 1
    names = [w["name"] for w in bench["workloads"]]
    assert names.count(CELL) == 1
    # appended: behind every cell that was there (a later PR's cells
    # come behind it in turn)
    assert names.index(CELL) > names.index("minicpm_sala_decode")
    cell = cells.resolve(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == (
        "slot_fleet_closed_loop")
    assert {m["name"] for m in cell.end_to_end} == {
        "infer_p99_ms", "peak_hbm_gib", "setup_s"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, better, source, layer, moves) in NEW_METRICS.items():
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (unit, better, source, layer, moves), name
        assert CELL in m["workloads"]
        cells.layer_metric_reader(name)         # the file is there
    # appended in this order, one block, behind every metric that was
    # there (what a later PR appends comes behind them and reddens
    # nothing here)
    order = [m["name"] for m in bench["per_layer"]]
    first = order.index(next(iter(NEW_METRICS)))
    assert order[first:first + len(NEW_METRICS)] == list(NEW_METRICS)
    assert first > order.index("device.idle_share_serve")
    for name in SHARED:
        listed = by_name[name]["workloads"]
        assert CELL in listed, name
        assert listed.index(CELL) > listed.index("minicpm_sala_decode"), name
    # wired to MiniCPM-SALA's counts: they do not gain the cell
    for name in ("server.step_mfu", "server.step_hbm_roofline"):
        assert CELL not in by_name[name]["workloads"]
    assert {m["name"] for m in cell.per_layer} == {
        "setup.compile_s", *SHARED, *NEW_METRICS}


def test_every_checks_module_imports_and_the_mix_reads_two_sessions():
    import numpy as np

    from benchmarks.traffic_kinds import slot_fleet_closed_loop as kind

    named = {k: importlib.import_module(v)
             for k, v in CONF["checks"].items()}
    assert set(named) == {"reference", "mapper", "check"}
    assert set(named["mapper"].DEPARTURES) <= set(
        named["reference"].Sizes._fields)
    assert not hasattr(named["mapper"], "UNSEPARATED")
    assert callable(named["check"].check_sessions)
    mix = cells.resolve(CELL).traffic
    assert mix["checked_quantiles"] == [0.0, 0.5]
    assert mix["checked_steps"] == 64
    prompts = kind.draw_sessions(2147483900, mix, 65_536, 128, 2_097_152)
    starts = np.asarray([len(x) for x in prompts])
    assert len(starts) == 256
    assert 1_024 <= starts.min() <= starts.max() <= 8_192
    assert 2_800 < starts.mean() < 4_000
    short, median = sorted(kind.checked_slots(starts, [0.0, 0.5]),
                           key=lambda i: starts[i])
    assert starts[short] == starts.min()
    assert starts[median] == np.sort(starts)[128]


def test_the_new_readers_return_nothing_where_there_is_nothing():
    """On the facts of a kind that has none of their inputs (another
    cell, a parent commit's program): no scope, no slot program among
    the trace's modules, no counters, no `decode` block."""
    class _Runtime:
        cell = cells.resolve("pong_live")
        devices = [type("D", (), {"device_kind": "TPU v5 lite"})()]

        @staticmethod
        def newest_xplane():
            return None

    facts = {"runtime": _Runtime, "window_s": 30.0,
             "server_window": {"batches": 100, "items": 1600},
             "trace": {"idle_share_worst": 0.5, "devices": [{
                 "busy_ns": 10 ** 9,
                 "modules": {"jit_apply": {"count": 9, "total_ns": 9,
                                           "median_ns": 1}}}]}}
    for name in NEW_METRICS:
        assert cells.layer_metric_reader(name).read(dict(facts)) is None, name
    # MiniCPM-SALA's cell has `decode`, `prefill` and counters of its own
    _Runtime.cell = cells.resolve("minicpm_sala_decode")
    sala = {**facts, "family": "minicpm_sala_slots",
            "decode": {"rows_per_step": 32.0, "contexts": [20_000.0] * 48},
            "prefill": {"tokens": 10 ** 6, "seconds": 50.0},
            "slot_counters": {"extend_tokens": 1600}}
    for name in NEW_METRICS:
        assert cells.layer_metric_reader(name).read(dict(sala)) is None, name
    # and on the cell's own facts without a trace's scopes
    _Runtime.cell = cells.resolve(CELL)
    own = {**facts, "family": counts.FAMILY,
           "decode": {"rows_per_step": 128.0, "contexts": [4_500.0] * 256},
           "prefill": {"tokens": 880_000, "seconds": 80.0},
           "slot_counters": {"ssm_rows_updated": 100 * 26 * 120}}
    assert cells.layer_metric_reader("ssm.rows_updated_per_step").read(
        dict(own)) == 120.0
    assert cells.layer_metric_reader("serve.prefill_tokens_per_s").read(
        dict(own)) == 11_000.0
    mfu = cells.layer_metric_reader("server.ssm_step_mfu").read(dict(own))
    assert 0.0 < mfu < 5.0
    for name in ("server.ssm_step_hbm_roofline", "serve.mamba_share",
                 "serve.ssm_scan_share", "kernels.ssm_state_roofline"):
        assert cells.layer_metric_reader(name).read(dict(own)) is None, name


def _tiny_run(monkeypatch, **mix) -> tuple[dict, dict]:
    import jax

    from ape_x_dqn_tpu.models import expert_layer

    jax.config.update("jax_enable_compilation_cache", False)
    # every matrix normal(0, 1 / sqrt(64)) where the cell's are
    # normal(0, 0.02) at hidden 2,560: a projection's output of order 1
    # at this width too, or a mixer is a thousandth of the stream and no
    # departure inside it is seen
    monkeypatch.setattr(expert_layer, "INIT_STD", 64 ** -0.5)
    cell = cells.resolve(CELL)
    cell = dataclasses.replace(cell,
                               traffic={**cell.traffic, **TRAFFIC, **mix})
    facts = {}
    real = cells.traffic_kind

    def spying(c):
        kind = real(c)

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
    from benchmarks.harness import jamba_params

    result, facts = _tiny_run(monkeypatch, show_limits=True)
    said = capsys.readouterr().err
    for name in (*jamba_params.DEPARTURES, "another_slots_state",
                 "one_bit_less"):
        assert f"'{name}': {{'passes': False" in said, name
    # the two that Q alone does not see, each by its own rule
    assert {"carry_rounded", "attn_one_short"} <= set(jamba_params.DEPARTURES)
    assert "'attn_one_short': {'passes': False, 'keys_ok': False" in said
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert set(result["metrics"]) == {"infer_p99_ms", "peak_hbm_gib",
                                      "setup_s"}
    json.dumps(result)
    assert result["correct"] and result["failed"] == 0, facts["checks"]
    assert set(facts["checks"]) == {
        "session_0_matches_reference", "session_1_matches_reference",
        "session_0_state_matches_reference",
        "session_1_state_matches_reference", "keys_attended_are_the_masks",
        "every_departure_is_refused", "no_query_failed",
        "extend_tokens_counter_is_what_was_sent",
        "slot_lengths_are_what_was_sent"}
    assert result["attempted"] == facts["query_latency_ms"]["count"] > 0
    assert facts["slot_ledger"]["slots_live"] == 4
    c = facts["slot_counters"]
    assert c["extend_tokens"] == facts["server_window"]["items"] > 0
    # four Mamba layers of the tiny stack, every row a valid one
    assert c["ssm_rows_updated"] == 4 * c["extend_tokens"]
    assert c["ssm_tokens_scanned"] == 0 and c["attn_positions_read"] > 0
    assert facts["family"] == counts.FAMILY
    assert len(facts["decode"]["contexts"]) == 4
