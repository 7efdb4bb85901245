"""The recurrent sizes of configs/r2d2_1chip.json against the preset,
the family's FLOP count against a hand count, and the seeded sequence
content under numpy and jax.numpy."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import cells, flops, flops_r2d2
from benchmarks.harness import sequence_content as sc

CONF = cells.resolve("r2d2_offline").config


def _cfg():
    from ape_x_dqn_tpu.configs import get_config
    from ape_x_dqn_tpu.runtime.train import apply_overrides

    return apply_overrides(get_config(CONF["preset"]), CONF["overrides"])


def test_sequence_sizes_are_what_preset_plus_overrides_build():
    cfg = _cfg()
    assert CONF["sequence_sizes"] == {
        "lstm_size": cfg.network.lstm_size,
        "seq_length": cfg.replay.seq_length,
        "seq_overlap": cfg.replay.seq_overlap,
        "burn_in": cfg.replay.burn_in,
        "priority_eta": cfg.replay.priority_eta,
        "value_rescale": cfg.learner.value_rescale,
        "target_sync_every": cfg.learner.target_sync_every,
        "lr": cfg.learner.lr,
    }
    assert cfg.network.kind == "lstm_q" and cfg.replay.kind == "sequence"
    assert CONF["family"] == flops_r2d2.FAMILY
    # the paper's widths, untouched
    assert (cfg.network.lstm_size, cfg.network.torso_dense,
            cfg.learner.batch_size) == (512, 512, 64)
    # what `assumed` says of the preset against the paper's table
    for key, value in (("learner.gamma", cfg.learner.gamma),
                       ("learner.adam_eps", cfg.learner.adam_eps),
                       ("replay.min_fill", cfg.replay.min_fill),
                       ("replay.alpha", cfg.replay.alpha),
                       ("replay.beta", cfg.replay.beta)):
        stated = CONF["assumed"][key].split(";")[0].replace(",", "")
        assert f"{value:g}" in stated, (key, stated)


def test_overrides_are_the_reduced_keys():
    keys = [o.split("=")[0] for o in CONF["overrides"]]
    assert keys + ["total_env_frames"] == CONF["reduced"]


def test_flops_against_a_hand_count_at_the_published_widths():
    """By hand: conv1 20x20x32 outputs x 8x8x4 = 3,276,800 MACs; conv2
    9x9x64 x 4x4x32 = 2,654,208; conv3 7x7x64 x 3x3x64 = 1,806,336;
    dense 3136 x 512 = 1,605,632; LSTM 4 gates x (512 + 512) x 512 =
    2,097,152; heads 512 x 7 = 3,584: 11,443,712 MACs a frame. Batch
    64 x (2 x 40 burn-in + 4 x 40 trained) = 15,360 forwards."""
    per_frame = (3_276_800 + 2_654_208 + 1_806_336 + 1_605_632
                 + 2_097_152 + 3_584)
    assert per_frame == 11_443_712
    assert flops_r2d2.frame_forward_macs(
        CONF["sizes"], CONF["sequence_sizes"]["lstm_size"]) == per_frame
    want = 2.0 * 15_360 * per_frame
    assert flops_r2d2.r2d2_lstm_dueling(
        CONF["sizes"], CONF["sequence_sizes"]) == want == 351_550_832_640.0
    # the traffic kind registers the family in the one table the
    # learner.mfu reader looks in, bound to the file's sequence sizes
    flops_r2d2.register(CONF["sequence_sizes"])
    assert flops.TRAIN_STEP_FLOPS[CONF["family"]](CONF["sizes"]) == want
    # the torso is the CNN family's: same MACs, counted by its function
    cnn = flops.nature_cnn_dueling_dqn({**CONF["sizes"], "batch_size": 1})
    assert cnn == 2.0 * 5.0 * (per_frame - 2_097_152 - 3_584 + 512 * 7)


def test_gather_bytes_from_shapes():
    """The useful bytes of an item, from the file's shapes alone: 83
    single frames of 84 x 84 B, 4 x 80 scalars of 4 B, 2 x 512 floats.
    No padding of the program's storage is counted (its rows are
    7,168 B for 7,056 B of frame)."""
    reader = cells.layer_metric_reader("replay.seq_gather_hbm_share")
    item = reader.item_bytes(CONF["sizes"], CONF["sequence_sizes"])
    assert item == 83 * 84 * 84 + 4 * 80 * 4 + 2 * 512 * 4 == 591_024
    assert reader.gather_bytes_per_step(64, item) == 2 * 64 * 591_024


@pytest.fixture(scope="module")
def content():
    from ape_x_dqn_tpu.envs import make_env

    cfg = _cfg()
    traffic = cells.resolve("r2d2_offline").traffic
    return sc.content(cfg, make_env(cfg.env, seed=0).spec, 2147483900,
                      traffic)


def test_sequence_content_is_the_same_bytes_under_numpy_and_jax(content):
    ids = np.array([0, 1, 63, 64, 4095, 16383], np.int32)
    host = sc.sequences(np, content, ids)
    dev = sc.sequences(jnp, content, jnp.asarray(ids))
    assert set(host) == set(sc.ITEM_KEYS) | {"priorities"}
    for k in sc.ITEM_KEYS:
        got = np.asarray(dev[k])
        assert got.dtype == host[k].dtype and got.shape == host[k].shape
        np.testing.assert_array_equal(got, host[k], err_msg=k)
    np.testing.assert_allclose(np.asarray(dev["priorities"]),
                               host["priorities"], rtol=1e-5)
    assert host["seq_frames"].shape == (6, 83, 84, 84)
    assert host["init_c"].shape == (6, 512)
    # a batch of ids gives what each id gives alone
    one = sc.sequences(np, content, np.array([4095], np.int32))
    np.testing.assert_array_equal(one["seq_frames"][0],
                                  host["seq_frames"][4])
    # another seed, other bytes
    other = sc.sequences(np, content._replace(seed=7), ids)
    assert (other["seq_frames"] != host["seq_frames"]).mean() > 0.9


def test_sequence_content_has_the_shapes_the_mix_states(content):
    """Fields only (frames are 7 kB a row): tails one sequence in 64
    with 41..79 valid steps ending in a terminal, padding zeroed,
    mid-sequence terminals one valid step in 2,048, state in +-0.5."""
    n = 16384
    ids = np.arange(n, dtype=np.int32)
    n_valid = sc.valid_length(np, content, ids)
    tails = n_valid < 80
    assert 0.7 * n / 64 < tails.sum() < 1.3 * n / 64
    assert n_valid[tails].min() == 41 and n_valid[tails].max() == 79
    assert len(np.unique(n_valid[tails])) == 39
    small = content._replace(geom=content.geom._replace(height=2, width=2))
    out = sc.sequences(np, small, ids)
    mask = out["mask"].astype(bool)
    np.testing.assert_array_equal(mask.sum(axis=1), n_valid)
    assert (mask[:, :-1] >= mask[:, 1:]).all()       # a prefix
    for k in ("actions", "rewards", "terminals"):
        assert (out[k][~mask] == 0).all()
    last = out["terminals"][np.arange(n), n_valid - 1]
    assert (last[tails] == 1).all()
    inside = out["terminals"].sum() - tails.sum()
    assert 0.6 < inside / (mask.sum() / 2048) < 1.4
    assert set(np.unique(out["rewards"])) == {-1.0, 0.0, 1.0}
    assert out["actions"].max() == content.geom.num_actions - 1
    for k in ("init_c", "init_h"):
        assert -0.5 <= out[k].min() < -0.49 and 0.49 < out[k].max() <= 0.5
    assert not np.array_equal(out["init_c"], out["init_h"])
    logp = np.log(out["priorities"] / 0.1)
    assert abs(logp.mean()) < 0.05 and 0.95 < logp.std() < 1.05


def test_mix_is_the_one_the_issue_names():
    traffic = cells.resolve("r2d2_offline").traffic
    assert {k: v for k, v in traffic.items() if k != "why"} == {
        "kind": "sequence_free_run", "ring_fill": 1.0,
        "fill_sequences_per_add": 64, "priority_lognormal_sigma": 1.0,
        "terminal_one_in": 2048, "episode_tail_one_in": 64,
        "init_state_scale": 0.5, "max_dispatches_in_flight": 16,
        "trace_window_s": 1.0}
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell = next(w for w in bench["workloads"]
                if w["name"] == "r2d2_offline")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "r2d2_1chip", "offline_seq", 1)
