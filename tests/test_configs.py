import jax
import pytest

from ape_x_dqn_tpu.configs import PRESETS, get_config
from ape_x_dqn_tpu.utils.rng import RngStream, component_key
from ape_x_dqn_tpu.utils.metrics import (
    Metrics, Throughput, human_normalized_score, median_hns,
    ATARI_HUMAN_RANDOM)


def test_eight_virtual_devices():
    assert jax.device_count() == 8


def test_five_presets_exist():
    # The five attested reference configs (SURVEY.md §2.1), and the
    # decoder Q-networks with their CPU-test siblings (PRs 30, 32, 39, 41,
    # 46, 50, 55, 57).
    assert set(PRESETS) == {
        "cartpole_smoke", "pong", "atari57_apex", "r2d2", "apex_dpg",
        "glm47_flash_q", "glm_tiny_q", "trinity_mini_q", "trinity_tiny_q",
        "smallthinker_21b_q", "smallthinker_tiny_q",
        "ouro_2p6b_q", "ouro_tiny_q",
        "kimi_linear_48b_q", "kimi_linear_tiny_q",
        "lfm2_24b_q", "lfm2_tiny_q",
        "minicpm_sala_9b_q", "minicpm_sala_tiny_q",
        "jamba2_3b_q", "jamba2_tiny_q"}


def test_preset_fields():
    cp = get_config("cartpole_smoke")
    assert cp.replay.kind == "uniform" and cp.actors.num_actors == 1
    pong = get_config("pong")
    assert pong.replay.kind == "prioritized" and pong.actors.num_actors == 8
    apex = get_config("atari57_apex")
    assert apex.actors.num_actors == 256
    assert apex.network.dueling and apex.learner.double_dqn
    r2d2 = get_config("r2d2")
    assert r2d2.replay.kind == "sequence"
    assert r2d2.replay.seq_length == 80 and r2d2.replay.burn_in == 40
    dpg = get_config("apex_dpg")
    assert dpg.network.kind == "dpg"


def test_config_override():
    cfg = get_config("pong", seed=7)
    assert cfg.seed == 7
    cfg2 = cfg.replace(total_env_frames=123)
    assert cfg2.total_env_frames == 123 and cfg.total_env_frames != 123


def test_unknown_config():
    with pytest.raises(KeyError):
        get_config("nope")


def test_rng_determinism():
    a = RngStream(0, "actor", 3)
    b = RngStream(0, "actor", 3)
    assert a.next_uint32() == b.next_uint32()
    c = RngStream(0, "actor", 4)
    assert a.next_uint32() != c.next_uint32()  # different actor index
    k1 = component_key(0, "learner")
    k2 = component_key(0, "replay")
    assert (jax.random.bits(k1, (), "uint32")
            != jax.random.bits(k2, (), "uint32"))


def test_metrics_and_throughput(tmp_path):
    m = Metrics(str(tmp_path / "log.jsonl"))
    m.log(1, loss=0.5, frames=100)
    assert m.latest()["loss"] == 0.5
    m.close()
    t = Throughput(window_s=100.0)
    t.add(10, now=0.0)
    t.add(10, now=1.0)
    assert abs(t.rate(now=1.0) - 20.0) < 1e-6


def test_metrics_tensorboard_sink(tmp_path):
    """Optional TB event-file sink (SURVEY.md §5 metrics row): scalars
    land in event files while JSONL stays canonical."""
    import pytest
    pytest.importorskip("torch.utils.tensorboard")
    tb_dir = tmp_path / "tb"
    m = Metrics(log_path=str(tmp_path / "log.jsonl"),
                tensorboard_dir=str(tb_dir))
    m.log(1, loss=0.5, note=None)  # non-scalars must be skipped, not die
    m.log(2, loss=0.25, frames=128)
    m.close()
    events = list(tb_dir.glob("events.out.tfevents.*"))
    assert events and events[0].stat().st_size > 0
    # JSONL canonical stream still intact
    import json
    recs = [json.loads(ln) for ln
            in (tmp_path / "log.jsonl").read_text().splitlines()]
    assert recs[-1]["loss"] == 0.25 and recs[-1]["frames"] == 128


def test_hns():
    assert len(ATARI_HUMAN_RANDOM) == 57
    assert abs(human_normalized_score("pong", 14.6) - 1.0) < 1e-9
    assert abs(median_hns({"pong": 14.6, "breakout": 30.5}) - 1.0) < 1e-9


def test_sample_chunk_gated_for_unimplemented_families():
    """Families without the K-batch relaxation must reject
    sample_chunk>1 loudly, not silently train exact semantics under a
    config that claims otherwise. (Round 5: the r2d2 family now
    runs K-batch — tests/test_r2d2_runtime.py covers its
    mechanics — so only DPG keeps the gate.)"""
    import pytest

    from ape_x_dqn_tpu.configs import LearnerConfig
    from ape_x_dqn_tpu.models import DPGActor, DPGCritic
    from ape_x_dqn_tpu.replay.prioritized import PrioritizedReplay
    from ape_x_dqn_tpu.runtime.dpg_learner import DPGLearner

    lcfg = LearnerConfig(batch_size=8, sample_chunk=4)
    actor = DPGActor(action_dim=1, action_low=-1, action_high=1)
    critic = DPGCritic()
    with pytest.raises(ValueError, match="sample_chunk"):
        DPGLearner(actor.apply, critic.apply,
                   PrioritizedReplay(capacity=64), lcfg)

    # same gate for the double-buffered sampling pipeline
    lcfg = LearnerConfig(batch_size=8, sample_prefetch=True)
    with pytest.raises(ValueError, match="sample_prefetch"):
        DPGLearner(actor.apply, critic.apply,
                   PrioritizedReplay(capacity=64), lcfg)


def test_final_eval_deadline_is_configurable():
    """The end-of-run eval backstop budget must come from RunConfig —
    a hard-coded 60s deadline silently discarded fully-trained suite
    games on slow-link hosts (round-5 suite-learning run: eval=null
    after 45k frames of training)."""
    from ape_x_dqn_tpu.configs import get_config

    cfg = get_config("pong")
    assert cfg.final_eval_deadline_s >= 300.0
    assert get_config("pong", final_eval_deadline_s=30.0) \
        .final_eval_deadline_s == 30.0
