"""BENCHMARK.json against the builder's contract, and every name in it
against the files the harness will look for."""

import json
import os
import re

import pytest

from benchmarks.harness import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}
BENCH = cells.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks"]
    assert BENCH["command"][:2] == ["python3", "benchmarks/run.py"]
    assert all(one_line(w) for w in BENCH["command"])
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    # 2 + 14 x cells runs of run_seconds + 60 s, 2 x 90 s per cell to
    # compile, 1200 s spare, within 43200 s — at the full 24 cells
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 \
        + 1200 <= 43200
    size = os.path.getsize(os.path.join(cells.ROOT, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_configs():
    assert 1 <= len(BENCH["configs"]) <= 24
    names = [c["name"] for c in BENCH["configs"]]
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert PATH.match(c["file"]) and c["file"].startswith(
            "benchmarks/")
        assert one_line(c["source"]) and one_line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(cells.ROOT, c["file"])) as fh:
            on_disk = json.load(fh)
        assert on_disk["reduced"] == c["reduced"]
        assert set(on_disk["reduced_why"]) == set(c["reduced"])


def test_workloads():
    ws = BENCH["workloads"]
    assert 2 <= len(ws) <= 24
    assert len(set(CELLS)) == len(CELLS)
    pairs = [(w["config"], w["traffic"]) for w in ws]
    assert len(set(pairs)) == len(pairs)
    for w in ws:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"])
    four = [w["name"] for w in ws if w["chips"] == 4]
    assert four == ["atari57_dp4_offline"]
    assert len(four) <= max(len(ws) // 4, 1)


def test_metrics():
    e2e, layer = BENCH["end_to_end"], BENCH["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    names = [m["name"] for m in e2e + layer]
    assert len(set(names)) == len(names)
    e2e_names = {m["name"] for m in e2e}
    assert "setup_s" in e2e_names
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in layer:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in SOURCES and one_line(m["layer"])
        assert m["moves"] in e2e_names
    for m in e2e + layer:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    # metrics of one layer give the same layer, letter for letter
    assert {m["layer"] for m in layer} == {
        "entry points", "drivers", "ingest", "learner", "replay",
        "inference", "parallel", "kernels (XLA)", "device"}


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_existing_files(name):
    cell = cells.resolve(name)
    assert cell.config["chips"] == cell.chips
    assert cell.config["name"] == cell.config_name
    assert os.path.isfile(os.path.join(
        cells.BENCH_DIR, "traffic_kinds", cell.traffic["kind"] + ".py"))
    assert hasattr(cells.traffic_kind(cell), "run")
    # every cell reports setup_s, another end-to-end metric and at
    # least one per-layer metric, each moving a metric of this cell
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e
        assert hasattr(cells.layer_metric_reader(m["name"]), "read")


def test_every_reader_file_is_declared_and_named_by_the_contract():
    declared = {m["name"] for m in BENCH["per_layer"]}
    on_disk = {f[:-3] for f in os.listdir(os.path.join(
        cells.BENCH_DIR, "layer_metrics")) if f.endswith(".py")}
    assert on_disk == declared
    for root, _, files in os.walk(cells.BENCH_DIR):
        if os.sep + "." in root or "__pycache__" in root:
            continue
        for f in files:
            assert PATH.match(f), os.path.join(root, f)


@pytest.mark.parametrize("name", sorted({w["config"]
                                         for w in BENCH["workloads"]}))
def test_config_file_states_the_sizes_that_run(name):
    """`sizes` in the file is what the preset plus overrides builds —
    the file cannot drift from the configuration as it is run."""
    from ape_x_dqn_tpu.configs import get_config
    from ape_x_dqn_tpu.envs import make_env
    from ape_x_dqn_tpu.utils.misc import next_pow2
    from ape_x_dqn_tpu.runtime.train import apply_overrides

    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    with open(os.path.join(cells.ROOT, entry["file"])) as fh:
        conf = json.load(fh)
    cfg = apply_overrides(get_config(conf["preset"]), conf["overrides"])
    spec = make_env(cfg.env, seed=0).spec
    want = {
        "frame": list(spec.obs_shape),
        "num_actions": spec.num_actions,
        "cnn_channels": list(cfg.network.cnn_channels),
        "cnn_kernels": list(cfg.network.cnn_kernels),
        "cnn_strides": list(cfg.network.cnn_strides),
        "torso_dense": cfg.network.torso_dense,
        "dueling": cfg.network.dueling,
        "compute_dtype": cfg.network.compute_dtype,
        "batch_size": cfg.learner.batch_size,
        "n_step": cfg.learner.n_step,
        "gamma": cfg.learner.gamma,
        "sample_chunk": cfg.learner.sample_chunk,
        "train_chunk": cfg.learner.train_chunk,
        "publish_every": cfg.learner.publish_every,
        "steps_per_frame_cap": cfg.learner.steps_per_frame_cap,
        "replay_capacity": cfg.parallel.dp * next_pow2(
            cfg.replay.capacity // cfg.parallel.dp),
        "replay_storage": cfg.replay.storage,
        "seg_transitions": cfg.replay.seg_transitions,
        "segs_per_add": cfg.replay.segs_per_add,
        "ingest_coalesce": cfg.replay.ingest_coalesce,
        "priority_alpha": cfg.replay.alpha,
        "priority_beta": cfg.replay.beta,
        "priority_eps": cfg.replay.eps,
        "inference_max_batch": cfg.inference.max_batch,
        "inference_deadline_ms": cfg.inference.deadline_ms,
    }
    assert conf["sizes"] == want
    assert conf["layout"]["dp"] == cfg.parallel.dp
    assert conf["layout"]["tp"] == cfg.parallel.tp
    assert cfg.parallel.dp * cfg.parallel.tp == conf["chips"]
    assert cfg.actors.num_actors == 0 and cfg.eval_episodes == 0
