"""Flight-recorder + postmortem forensics coverage (ISSUE 17): ring
bounding and drop accounting, the crash/stall/SIGUSR2 dump paths, the
torn-partial tolerance of the bundler, merge ordering across two
real-socket peers with retained telemetry frames, `report
--postmortem` root-cause naming, the `--check` forensics rows, and
the disabled-config no-op contract."""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from ape_x_dqn_tpu.comm.socket_transport import (
    SocketIngestServer, SocketTransport)
from ape_x_dqn_tpu.configs import ObsConfig
from ape_x_dqn_tpu.obs import postmortem, report
from ape_x_dqn_tpu.obs.blackbox import (
    NULL_BLACKBOX, FlightRecorder, default_peer)
from ape_x_dqn_tpu.obs.core import NULL_OBS, build_obs
from ape_x_dqn_tpu.obs.fleet import (
    FleetAggregator, StampingTransport, TelemetryEmitter)
from ape_x_dqn_tpu.obs.health import StallError
from ape_x_dqn_tpu.utils.metrics import Metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Sink:
    """Minimal obs facade: the recorder only needs .count."""

    def __init__(self):
        self.ctr: dict[str, int] = {}

    def count(self, name, n=1):
        self.ctr[name] = self.ctr.get(name, 0) + n


def _experience_batch(n=8, seed=0):
    rng = np.random.default_rng(seed)
    return {"obs": rng.random((n, 4)).astype(np.float32),
            "action": rng.integers(0, 2, (n,)).astype(np.int32),
            "priorities": (rng.random(n) + 0.1).astype(np.float32),
            "actor": 0, "frames": n}


def _wait(predicate, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.01)
    return False


# -- ring bounding ----------------------------------------------------------

def test_ring_bounds_and_drop_accounting(tmp_path):
    """50 records through a capacity-8 ring: the dump holds exactly
    the LAST 8, the 42 overwrites are counted as drops, and the
    published counters agree with the ring's own accounting."""
    sink = _Sink()
    rec = FlightRecorder(sink, peer="p0", out_dir=str(tmp_path),
                         capacity=8)
    for i in range(50):
        rec.record("publish", step=i)
    path = rec.dump("test")
    assert path and os.path.exists(path)
    d = json.load(open(path))
    assert [r["step"] for r in d["records"]] == list(range(42, 50))
    assert d["recorded"] == 50 and d["dropped"] == 42
    assert sink.ctr["blackbox_records"] == 50
    assert sink.ctr["blackbox_dropped"] == 42
    assert sink.ctr["blackbox_dumps"] == 1


def test_dump_payload_is_complete_and_atomic(tmp_path):
    """A dump carries the ring, the log tail, per-thread stacks, and
    provider context — and leaves no .tmp behind."""
    sink = _Sink()
    rec = FlightRecorder(sink, peer="p1", out_dir=str(tmp_path))
    rec.record("wedge", component="sender-0")
    rec.log_line("last words")
    rec.add_context_provider(lambda: {"transport": {"reconnects": 3}})
    path = rec.dump("sigusr2", component="sender-0", step=7,
                    extra={"note": "drill"})
    d = json.load(open(path))
    assert d["blackbox"] == 1 and d["peer"] == "p1"
    assert d["reason"] == "sigusr2" and d["step"] == 7
    assert d["records"][0]["kind"] == "wedge"
    assert d["records"][0]["component"] == "sender-0"
    assert d["log_tail"][-1][1] == "last words"
    assert d["transport"] == {"reconnects": 3}
    assert d["extra"] == {"note": "drill"}
    # every live thread contributes a stack snapshot
    assert threading.current_thread().name in d["threads"]
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]
    assert default_peer().endswith(f"-{os.getpid()}")


# -- crash paths ------------------------------------------------------------

def test_stall_error_archives_the_ring(tmp_path):
    """check_stalled: the StallError is dumped (reason=stall, the
    stale component named) BEFORE the obs closes and the error
    propagates — and the run JSONL cross-references the dump so
    `report --check`'s forensics row can demand it."""
    jsonl = str(tmp_path / "run.jsonl")
    metrics = Metrics(log_path=jsonl)
    obs = build_obs(ObsConfig(enabled=True, heartbeat_timeout_s=0.05,
                              blackbox_dir=str(tmp_path)), metrics)
    obs.beat("learner", "step 3")
    time.sleep(0.12)
    with pytest.raises(StallError):
        obs.check_stalled()
    metrics.close()
    dump_path = obs.blackbox.path
    assert os.path.exists(dump_path)
    d = json.load(open(dump_path))
    assert d["reason"] == "stall" and d["component"] == "learner"
    assert any(r["kind"] == "stall" for r in d["records"])
    recs = [json.loads(l) for l in open(jsonl)]
    s = report.summarize(recs)
    assert s["stalls"] and s["blackbox_dumps"]
    assert s["blackbox_dumps"][0]["path"] == dump_path
    # dump on disk: the forensics row is satisfied
    assert not [v for v in report.check_violations(s)
                if v.startswith("blackbox_dumps")]


def test_unhandled_crash_dumps_via_excepthook(tmp_path):
    """A raising loop in a real child process: the chained excepthook
    archives the ring with the exception type as the component and
    the traceback in extra, then the process still dies nonzero."""
    code = (
        "from ape_x_dqn_tpu.obs.blackbox import FlightRecorder\n"
        "class S:\n"
        "    def count(self, name, n=1): pass\n"
        f"rec = FlightRecorder(S(), peer='crasher', "
        f"out_dir={str(tmp_path)!r})\n"
        "rec.install(signals=False)\n"
        "rec.record('actor_error', component='actor-3', error='boom')\n"
        "raise ValueError('boom')\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=60)
    assert p.returncode != 0
    assert "ValueError: boom" in p.stderr  # chained to the default hook
    d = json.load(open(tmp_path / "blackbox-crasher.json"))
    assert d["reason"] == "crash" and d["component"] == "ValueError"
    assert any(r["kind"] == "crash" for r in d["records"])
    assert any("boom" in line for line in d["extra"]["traceback"])


@pytest.mark.skipif(not hasattr(signal, "SIGUSR2"),
                    reason="no SIGUSR2 on this platform")
def test_sigusr2_dumps_live_without_dying(tmp_path):
    """The live 'explain yourself' path: SIGUSR2 dumps the ring and
    the process keeps running; uninstall restores the old handler."""
    if threading.current_thread() is not threading.main_thread():
        pytest.skip("signal installation needs the main thread")
    sink = _Sink()
    rec = FlightRecorder(sink, peer="live", out_dir=str(tmp_path))
    prev = signal.getsignal(signal.SIGUSR2)
    rec.install()
    try:
        rec.record("publish", step=1)
        os.kill(os.getpid(), signal.SIGUSR2)
        assert _wait(lambda: os.path.exists(rec.path))
        d = json.load(open(rec.path))
        assert d["reason"] == "sigusr2"
        assert any(r["kind"] == "sigusr2" for r in d["records"])
    finally:
        rec.uninstall()
    assert signal.getsignal(signal.SIGUSR2) == prev


# -- bundler ----------------------------------------------------------------

def test_torn_partial_is_skipped_counted_and_named(tmp_path):
    """A kill mid-dump leaves a torn file (and maybe a stray .tmp):
    the bundler skips BOTH, counts them, names them — and still
    bundles the good dumps."""
    sink = _Sink()
    rec = FlightRecorder(sink, peer="good", out_dir=str(tmp_path))
    rec.record("wedge", component="sender-0")
    rec.dump("drill")
    (tmp_path / "blackbox-torn.json").write_text('{"peer": "torn", ')
    (tmp_path / "blackbox-killed.json.tmp").write_text('{"pe')
    bundle = postmortem.build_bundle(
        str(tmp_path), out_path=str(tmp_path / "POSTMORTEM.json"),
        obs=sink)
    assert [d["peer"] for d in bundle["dumps"]] == ["good"]
    skipped = {s["file"]: s["reason"] for s in bundle["skipped_dumps"]}
    assert skipped["blackbox-torn.json"] == "truncated/unparseable"
    assert "incomplete" in skipped["blackbox-killed.json.tmp"]
    assert sink.ctr["postmortem_bundles"] == 1
    ondisk = json.load(open(bundle["path"]))
    assert ondisk["postmortem"] == 1
    assert len(ondisk["skipped_dumps"]) == 2


def test_bundle_merges_two_socket_peers_in_causal_order(tmp_path):
    """Two actor hosts over REAL loopback sockets, each with its own
    flight recorder; the learner's aggregator retains their last
    telemetry frames. The bundle merges dumps + run JSONL + frames
    into one wall-clock-sorted timeline, and the root-cause walk
    blames peer A's wedge for peer B's later terminal error."""
    jsonl = str(tmp_path / "run.jsonl")
    learner_metrics = Metrics(log_path=jsonl)
    learner_obs = build_obs(
        ObsConfig(enabled=True, heartbeat_timeout_s=0.0,
                  blackbox_dir=str(tmp_path)), learner_metrics)
    server = SocketIngestServer("127.0.0.1", 0)
    agg = FleetAggregator(learner_obs)
    assert agg.install(server)
    peers = ["hostA-1-a0", "hostB-2-a1"]
    actors = []
    try:
        for name in peers:
            actor_obs = build_obs(
                ObsConfig(enabled=True, heartbeat_timeout_s=0.0,
                          blackbox_dir=str(tmp_path)), Metrics())
            actor_obs.blackbox.set_peer(name)
            client = SocketTransport("127.0.0.1", server.port)
            stamper = StampingTransport(client, name)
            emitter = TelemetryEmitter(stamper, actor_obs, name,
                                       interval_s=0)
            stamper.send_experience(_experience_batch())
            assert server.recv_experience(timeout=5.0) is not None
            assert emitter.pump_once()
            actors.append((actor_obs, client))
        assert _wait(lambda: sorted(agg.peers) == peers)
        # the incident: A wedges, then B dies — each archives its ring
        obs_a, obs_b = actors[0][0], actors[1][0]
        obs_a.blackbox.record("wedge", component="sender-0")
        assert obs_a.blackbox.dump("supervisor_request")
        time.sleep(0.05)
        obs_b.blackbox.record("actor_error", component="actor-1",
                              error="RuntimeError('dead')")
        assert obs_b.blackbox.dump("actor_error", component="actor-1")
        frames = agg.retained_frames()
        assert sorted(frames) == peers
        for st in frames.values():
            assert isinstance(st["frame"], dict)
            assert st["recv_unix"] > 0 and st["connected"]
        bundle = postmortem.build_bundle(
            str(tmp_path), jsonl_path=jsonl, frames=frames,
            out_path=str(tmp_path / "POSTMORTEM.json"),
            obs=learner_obs)
    finally:
        for actor_obs, client in actors:
            client.close()
        server.stop()
        for actor_obs, client in actors:
            actor_obs.close()
        learner_obs.close()
        learner_metrics.close()
    assert sorted(bundle["peers"]) == peers
    ts = [e["t"] for e in bundle["timeline"]]
    assert ts == sorted(ts)
    kinds = {(e["kind"], e["peer"]) for e in bundle["timeline"]}
    assert ("telemetry_frame", peers[0]) in kinds
    assert ("telemetry_frame", peers[1]) in kinds
    root = report.postmortem_root_cause(bundle)
    assert root["terminal"]["kind"] == "actor_error"
    assert root["terminal"]["peer"] == peers[1]
    assert root["anomaly"]["kind"] == "wedge"
    assert root["anomaly"]["component"] == "sender-0"
    assert root["gap_s"] > 0


# -- report --postmortem ----------------------------------------------------

def test_report_postmortem_names_root_cause(tmp_path, capsys):
    """The CLI on a synthetic bundle: the inventory names the skipped
    partial, and the final line walks back from the terminal
    quarantine to the wedge that preceded it."""
    sink = _Sink()
    rec_a = FlightRecorder(sink, peer="actor-7", out_dir=str(tmp_path))
    rec_a.record("wedge", component="sender-0")
    rec_a.dump("supervisor_request")
    time.sleep(0.02)
    rec_d = FlightRecorder(sink, peer="driver-1", out_dir=str(tmp_path))
    rec_d.record("quarantine", component="actor-7", staleness_s=9.0)
    rec_d.dump("quarantine", component="actor-7")
    (tmp_path / "blackbox-torn.json").write_text('{"peer": "to')
    bpath = str(tmp_path / "POSTMORTEM.json")
    postmortem.build_bundle(str(tmp_path), out_path=bpath)
    assert report.main([bpath, "--postmortem"]) == 0
    out = capsys.readouterr().out
    assert "skipped dump: blackbox-torn.json" in out
    last = out.strip().splitlines()[-1]
    assert last.startswith("root cause:")
    assert "wedge" in last and "component=sender-0" in last
    assert "quarantine" in last and "component=actor-7" in last
    # --json mode: machine-checkable attribution for the chaos lane
    assert report.main([bpath, "--postmortem", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["root_cause"]["anomaly"]["component"] == "sender-0"
    assert doc["dumps"] == 2 and len(doc["skipped_dumps"]) == 1


# -- --check forensics rows -------------------------------------------------

def test_check_demands_dump_for_terminal_events(tmp_path):
    """A terminal quarantine with NO black-box dump on disk fails
    --check naming the component; the same stream plus a dump that
    exists passes the forensics row."""
    recs = [{"step": 1, "time": 1.0, "actor_quarantined": 3,
             "stall_staleness_s": 7.0}]
    v = [x for x in report.check_violations(report.summarize(recs))
         if x.startswith("blackbox_dumps")]
    assert len(v) == 1 and "quarantine:actor-3" in v[0]
    dump = tmp_path / "blackbox-driver-1.json"
    dump.write_text("{}")
    recs.append({"step": 1, "time": 1.1,
                 "blackbox_dump": str(dump),
                 "blackbox_reason": "quarantine",
                 "blackbox_peer": "driver-1",
                 "blackbox_component": "actor-3"})
    assert not [x for x in
                report.check_violations(report.summarize(recs))
                if x.startswith("blackbox_dumps")]


def test_check_flags_dump_that_lost_its_window(tmp_path):
    """Per-dump ring-drop row: a dump that overwrote most of its ring
    before dumping is flagged; normal steady-state overwriting on a
    healthy dump is not."""
    dump = tmp_path / "blackbox-p.json"
    dump.write_text("{}")
    base = {"step": 1, "time": 1.0, "blackbox_dump": str(dump),
            "blackbox_reason": "stall"}
    lossy = dict(base, blackbox_ring_recorded=100,
                 blackbox_ring_dropped=80)
    v = [x for x in report.check_violations(report.summarize([lossy]))
         if x.startswith("blackbox_dropped")]
    assert len(v) == 1 and "blackbox_capacity" in v[0]
    healthy = dict(base, blackbox_ring_recorded=100,
                   blackbox_ring_dropped=20)
    assert not [x for x in
                report.check_violations(report.summarize([healthy]))
                if x.startswith("blackbox_dropped")]


# -- a healthy run ----------------------------------------------------------

def test_healthy_training_run_leaves_no_dump(tmp_path):
    """The recorder rides a real run for free of side effects: a short
    synthetic-Atari run through the single-process driver with the
    recorder live (crash hooks installed; publish, stall and perf
    events going into the ring) writes no blackbox-*.json, and
    obs.close() has taken the crash hooks out again."""
    from ape_x_dqn_tpu.configs import (
        EnvConfig, LearnerConfig, NetworkConfig, ReplayConfig, get_config)
    from ape_x_dqn_tpu.runtime.single_process import train_single_process

    hook = sys.excepthook
    cfg = get_config("pong").replace(
        env=EnvConfig(id="catch", kind="synthetic_atari"),
        network=NetworkConfig(kind="nature_cnn", dueling=True,
                              compute_dtype="float32"),
        replay=ReplayConfig(kind="prioritized", capacity=2048,
                            min_fill=300),
        learner=LearnerConfig(batch_size=16, n_step=3,
                              target_sync_every=16, sample_chunk=2),
        obs=ObsConfig(enabled=True, publish_every_steps=50,
                      heartbeat_timeout_s=120.0, blackbox=True,
                      blackbox_dir=str(tmp_path)))
    out = train_single_process(cfg, total_env_frames=600,
                               metrics=Metrics(), train_every=2)
    assert out["grad_steps"] > 0
    assert not [f for f in os.listdir(tmp_path)
                if f.startswith("blackbox-")]
    assert sys.excepthook is hook


# -- disabled contract ------------------------------------------------------

def test_disabled_blackbox_is_a_noop(tmp_path):
    """ObsConfig.blackbox=False: the facade carries NULL_BLACKBOX —
    recording and dumping do nothing, no files appear, and the
    config-off contract matches NULL_OBS (build_obs(None, ...))."""
    obs = build_obs(ObsConfig(enabled=True, blackbox=False,
                              blackbox_dir=str(tmp_path)), Metrics())
    assert obs.blackbox is NULL_BLACKBOX
    obs.blackbox.record("wedge", component="x")
    obs.blackbox.log_line("nope")
    assert obs.blackbox.dump("test") is None
    obs.blackbox.install()
    obs.publish(1)  # the publish anchor must not revive the recorder
    obs.close()
    assert not [f for f in os.listdir(tmp_path)
                if f.startswith("blackbox-")]
    assert NULL_OBS.blackbox is NULL_BLACKBOX
    assert build_obs(None, Metrics()) is NULL_OBS
