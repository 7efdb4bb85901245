"""The configuration `minicpm_sala_9b_pp4_1chip`, the mix
`decode_sessions_long` and the cell `minicpm_sala_decode` (PR 55):
the file's sizes are what the preset plus the overrides build and hold
the catalog row's keys; the counts of a decode step by hand; the cell
and its metrics are declared; every new reader returns nothing on
facts without its inputs; and the kind runs end to end at tiny widths
on the CPU, through `runner.run_cell`, `correct` true as the cell runs
it and every reading that has to fail failing under `show_limits`."""

import dataclasses
import json
import os
import time

import pytest

from benchmarks.harness import cells, runner
from benchmarks.harness import flops_minicpm_sala as counts

CELL = "minicpm_sala_decode"
CONF = cells.resolve(CELL).config
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
HELD = ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"]
NEW_METRICS = {
    "server.decode_step_ms": ("ms", "lower", "device_trace", "inference"),
    "server.step_mfu": ("%", "higher", "host_clock", "inference"),
    "server.step_hbm_roofline": ("%", "higher", "device_trace",
                                 "inference"),
    "serve.sparse_share": ("%", "lower", "device_trace", "inference"),
    "serve.lightning_share": ("%", "lower", "device_trace", "inference"),
    "kernels.sparse_decode_roofline": ("%", "higher", "device_trace",
                                       "kernels (XLA)"),
    "kernels.lightning_state_roofline": ("%", "higher", "device_trace",
                                         "kernels (XLA)"),
    "sparse.attended_share": ("%", "lower", "program_counter", "inference"),
    "device.idle_share_serve": ("%", "lower", "device_trace", "device")}
# the tiny preset's widths on the cell's preset, and a server to match
TINY = (
    "network.minicpm_sala.hidden_size=64",
    "network.minicpm_sala.num_hidden_layers=4",
    "network.minicpm_sala.mixer_types=('minicpm4','lightning-attn',"
    "'lightning-attn','minicpm4')",
    "network.minicpm_sala.num_attention_heads=4",
    "network.minicpm_sala.num_key_value_heads=1",
    "network.minicpm_sala.head_dim=16",
    "network.minicpm_sala.lightning_nh=4",
    "network.minicpm_sala.lightning_nkv=4",
    "network.minicpm_sala.lightning_head_dim=16",
    "network.minicpm_sala.intermediate_size=96",
    "network.minicpm_sala.dim_model_base=16",
    "network.minicpm_sala.vocab_size=64", "env.num_tokens=64",
    "network.minicpm_sala.sparse_block_size=8",
    "network.minicpm_sala.sparse_kernel_size=4",
    "network.minicpm_sala.sparse_kernel_stride=2",
    "network.minicpm_sala.sparse_window_size=16",
    "network.minicpm_sala.sparse_topk=6",
    "network.minicpm_sala.sparse_dense_len=32",
    "inference.slots=4", "inference.slot_max_len=4072",
    "inference.slot_pool_tokens=16384", "inference.prefill_chunk=16",
    "inference.prefill_rows=2", "inference.max_batch=4")
TRAFFIC = {"clients": 2, "sessions_per_client": 2, "start_min": 40,
           "start_max": 72, "decode_max": 4000, "settle_s": 0.2,
           "checked_steps": 4}


def _cfg():
    from ape_x_dqn_tpu.configs import get_config
    from ape_x_dqn_tpu.runtime.train import apply_overrides

    return apply_overrides(get_config(CONF["preset"]), CONF["overrides"])


def test_model_sizes_are_what_preset_plus_overrides_build():
    from ape_x_dqn_tpu.models import build_network
    from ape_x_dqn_tpu.runtime import family as fam

    cfg = _cfg()
    sala = cfg.network.minicpm_sala
    m = CONF["model_sizes"]
    for key, value in dataclasses.asdict(sala).items():
        assert m[key] == (list(value) if isinstance(value, tuple)
                          else value), key
    assert m["mixer_types"] == HELD
    net = build_network(cfg.network, None)
    assert net.param_count() == m["parameters"] == 2_820_569_088
    assert CONF["sizes"]["num_actions"] == net.num_actions == 73_448
    assert cfg.network.compute_dtype == CONF["sizes"]["compute_dtype"]
    inf, s, server = cfg.inference, CONF["sizes"], CONF["server_sizes"]
    # the server's settings are THE PRESET'S (ISSUE 55): no override
    from ape_x_dqn_tpu.configs import get_config
    preset = get_config(CONF["preset"]).inference
    assert not [o for o in CONF["overrides"] if o.startswith(
        ("inference.max_batch", "inference.deadline_ms"))]
    assert (inf.max_batch, inf.deadline_ms) == (
        s["inference_max_batch"], s["inference_deadline_ms"]) == (
        preset.max_batch, preset.deadline_ms) == (64, 2.0)
    assert server == {
        "slots": inf.slots, "slot_max_len": inf.slot_max_len,
        "slot_pool_tokens": inf.slot_pool_tokens,
        "prefill_chunk": inf.prefill_chunk, "prefill_rows": inf.prefill_rows}
    # the mix's sessions fit the server the file builds
    mix = cells.resolve(CELL).traffic
    assert mix["clients"] * mix["sessions_per_client"] <= inf.slots
    assert mix["start_max"] + mix["decode_max"] == inf.slot_max_len
    assert fam.slot_geometry(cfg, 64) == (48, 49_152, 1_671_168)
    # 5.25 GiB of bfloat16 parameters, 12 MiB and 2,112 B a position
    assert round(2 * m["parameters"] / 2 ** 30, 2) == 5.25
    assert net.slot_state_bytes(48, 1_671_168, 49_152) == (
        49 * (12 * 2 ** 20 + 4) + (1_671_168 + 49_152) * 2112)


def test_correct_reads_the_shortest_and_the_median_session_by_name():
    """The mix holds a session past the shortest to the reference (the
    median: twice the blocks in context), over 64 timed steps each; and
    the configuration names the modules that do it."""
    import importlib

    import numpy as np

    from benchmarks.traffic_kinds import slot_sessions_closed_loop as kind

    mix = cells.resolve(CELL).traffic
    assert mix["checked_quantiles"] == [0.0, 0.5]
    assert mix["checked_steps"] == 64
    prompts = kind.draw_sessions(7, mix, 73_448, 64, 1_671_168)
    starts = np.asarray([len(x) for x in prompts])
    short, median = sorted(kind.checked_slots(starts, [0.0, 0.5]),
                           key=lambda i: starts[i])
    assert starts[short] == starts.min() >= mix["start_min"]
    assert starts[median] == np.sort(starts)[24] > 1.4 * starts[short]
    named = {k: importlib.import_module(v)
             for k, v in CONF["checks"].items()}
    assert set(named) == {"reference", "mapper", "check"}
    assert set(named["mapper"].DEPARTURES) <= set(
        named["reference"].Sizes._fields)
    assert callable(named["check"].check_sessions)


def test_the_file_holds_the_catalog_rows_keys():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "MiniCPM-SALA")
    assert CONF["source"].startswith(row["source_url"])
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == CONF["name"])
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == CONF["reduced"]
    assert set(CONF["reduced_why"]) == set(CONF["reduced"])
    for key, published in row["config"].items():
        if key == "num_hidden_layers":
            assert (published, CONF[key]) == (32, 8)
            assert CONF["published"][key] == published
        else:
            assert CONF[key] == published, key
    m = CONF["model_sizes"]
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "lightning_nh",
                "lightning_nkv", "lightning_head_dim", "vocab_size",
                "rms_norm_eps", "rope_theta", "scale_emb", "scale_depth",
                "dim_model_base", "max_position_embeddings", "qk_norm",
                "use_output_gate", "use_output_norm",
                "attn_use_output_gate", "attn_use_rope",
                "lightning_use_rope"):
        assert m[key] == row["config"][key], key
    # the published layers 9-16, and the depth the residual scale keeps
    assert m["mixer_types"] == row["config"]["mixer_types"][9:17]
    assert m["depth_scale_layers"] == row["config"]["num_hidden_layers"]
    # every (+) of the issue is stated
    assert sum(k.startswith("(+) ") for k in CONF["assumed"]) == 7


def test_the_counts_of_a_decode_step_by_hand():
    """ISSUE 55's arithmetic at 48 rows of 21,300 positions."""
    m = CONF["model_sizes"]
    sparse = 4096 * (3 * 4096 + 2 * 256) + 3 * 4096 * 16384
    lightning = 5 * 4096 * 4096 + 3 * 4096 * 16384
    assert counts.matrix_params(m) == (2 * sparse + 6 * lightning
                                       + 4096 * 73_448)
    contexts = [21_300.0] * 48
    gib = 2.0 ** 30
    assert round(2 * counts.matrix_params(m) / gib, 2) == 4.69
    assert counts.lightning_bytes(m, 48) / gib == 1.125
    assert round(counts.sparse_bytes(m, contexts) / gib, 2) == 0.44
    assert round(counts.step_bytes(m, contexts) / 819e9 * 1e3, 1) == 8.2
    # below dense_len every position is attended and nothing is scored
    assert counts.attended_positions(m, 5_000) == 5_000
    assert counts.visible_windows(m, 5_000) == 0
    assert counts.attended_positions(m, 21_300) == 4_096
    assert counts.visible_windows(m, 21_300) == (21_300 - 32) // 16 + 1
    per_row = 2 * counts.matrix_params(m) + 6 * 5 * 32 * 128 * 128
    attention = 2 * 32 * 128 * (2 * 1_330 + 4 * 4_096)
    assert counts.step_flops(m, [21_300.0]) == per_row + attention


def test_the_cell_and_its_metrics_are_declared():
    bench = cells.load_benchmark()
    assert [c["name"] for c in bench["configs"]].count(CONF["name"]) == 1
    cell = cells.resolve(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == (
        "slot_sessions_closed_loop")
    assert {m["name"] for m in cell.end_to_end} == {
        "infer_p99_ms", "peak_hbm_gib", "setup_s"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, better, source, layer) in NEW_METRICS.items():
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            unit, better, source, layer), name
        assert m["moves"] == "infer_p99_ms" and m["workloads"] == [CELL]
        cells.layer_metric_reader(name)         # the file is there
    # appended, nothing in the middle
    assert [m["name"] for m in bench["per_layer"][-9:]] == list(NEW_METRICS)
    for name in ("server.p50_ms", "server.queue_wait_ms",
                 "driver.gc_pause_ms"):
        assert by_name[name]["workloads"] == ["pong_live", CELL]
    assert {m["name"] for m in cell.per_layer} == {
        "setup.compile_s", "server.p50_ms", "server.queue_wait_ms",
        "driver.gc_pause_ms", *NEW_METRICS}


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
    # and on the cell's own facts without a trace's scopes
    _Runtime.cell = cells.resolve(CELL)
    own = {**facts, "decode": {"rows_per_step": 32.0,
                               "contexts": [20_000.0] * 48},
           "slot_counters": {"sparse_blocks_attended": 64,
                             "sparse_blocks_in_context": 320}}
    assert cells.layer_metric_reader("sparse.attended_share").read(
        dict(own)) == 20.0
    assert cells.layer_metric_reader("device.idle_share_serve").read(
        dict(own)) == 50.0
    mfu = cells.layer_metric_reader("server.step_mfu").read(dict(own))
    assert 0.0 < mfu < 1.0
    for name in ("server.decode_step_ms", "server.step_hbm_roofline",
                 "serve.sparse_share", "kernels.sparse_decode_roofline",
                 "kernels.lightning_state_roofline"):
        assert cells.layer_metric_reader(name).read(dict(own)) is None, name


def _tiny_run(monkeypatch, **mix) -> tuple[dict, dict]:
    import jax

    from ape_x_dqn_tpu.models import expert_layer

    jax.config.update("jax_enable_compilation_cache", False)
    # every matrix normal(0, 1 / sqrt(64)) where the cell's are
    # normal(0, 0.02) at hidden 4,096: a projection's output of order 1
    # at this width too, or a sparse mixer is a ten-thousandth of the
    # stream and no departure inside it is seen
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
    from benchmarks.harness import minicpm_sala_params

    result, facts = _tiny_run(monkeypatch, show_limits=True)
    said = capsys.readouterr().err
    for name in (*minicpm_sala_params.DEPARTURES, "another_slots_state"):
        assert f"'{name}': {{'passes': False" in said, name
    assert "'one_bit_less': {'passes': " in said
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert set(result["metrics"]) == {"infer_p99_ms", "peak_hbm_gib",
                                      "setup_s"}
    json.dumps(result)
    assert result["correct"] and result["failed"] == 0, facts["checks"]
    assert set(facts["checks"]) == {
        "session_0_matches_reference", "session_1_matches_reference",
        "every_departure_is_refused", "no_query_failed",
        "extend_tokens_counter_is_what_was_sent",
        "slot_lengths_are_what_was_sent"}
    assert result["attempted"] == facts["query_latency_ms"]["count"] > 0
    assert facts["slot_ledger"]["slots_live"] == 4
    c = facts["slot_counters"]
    assert c["extend_tokens"] == facts["server_window"]["items"] > 0
    assert 0 < c["sparse_blocks_attended"] <= c["sparse_blocks_in_context"]
    assert facts["family"] == counts.FAMILY
    assert len(facts["decode"]["contexts"]) == 4
