"""apexlint gate + checker self-tests + lock-order witness tests.

Three layers:
- the tier-1 gate: the CLI over the real package must report ZERO
  findings (waivers are allowed — they are justified in-line);
- checker calibration: the deliberately-broken fixtures under
  tests/apexlint_fixtures/ must each produce exactly the expected
  finding, and the good twins exactly none (a checker that goes quiet
  or noisy fails here, not silently in review);
- the dynamic companion: the lock-order witness must raise on an
  A->B / B->A acquisition cycle and stay silent on consistent order.
"""

import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO_ROOT, "tests", "apexlint_fixtures")

sys.path.insert(0, REPO_ROOT)  # tools/ is repo-local, not installed

from tools.apexlint import run as apexlint_run  # noqa: E402
from tools.apexlint import config_coverage, counter_closure, guarded_by, \
    host_sync, jit_purity, learner_parity, obs_names, \
    remediation_accounting, resource_lifecycle, retry_annotation, \
    thread_lifecycle, use_after_donate, wire_protocol  # noqa: E402


def _fx(name: str) -> str:
    return os.path.join(FIXTURES, name)


# -- the tier-1 gate ------------------------------------------------------

def test_package_has_zero_findings():
    summary = apexlint_run(os.path.join(REPO_ROOT, "ape_x_dqn_tpu"))
    assert summary["findings"] == [], (
        "apexlint found violations in the package:\n" + "\n".join(
            f"{f['path']}:{f['line']}: [{f['checker']}] {f['message']}"
            for f in summary["findings"]))
    # waivers exist (each justified in-line); the summary counts them
    assert summary["checked_files"] > 50


def test_cli_json_subprocess():
    out = subprocess.run(
        [sys.executable, "-m", "tools.apexlint", "ape_x_dqn_tpu/",
         "--format=json"],
        capture_output=True, text=True, timeout=120, cwd=REPO_ROOT)
    assert out.returncode == 0, out.stdout + out.stderr
    summary = json.loads(out.stdout)
    assert summary["findings"] == []
    assert set(summary["per_checker"]) == {
        "guarded-by", "jit-purity", "wire-protocol", "obs-names",
        "retry-annotation", "remediation-accounting",
        "use-after-donate", "host-sync",
        "config-coverage", "learner-parity",
        "thread-lifecycle", "resource-lifecycle", "counter-closure"}
    # per-checker shape is what the CLI prints and run_chunked.sh
    # asserts on; "ms" is the wall-clock to watch for a checker gone slow
    for counts in summary["per_checker"].values():
        assert set(counts) == {"findings", "waivers", "ms"}
        assert counts["ms"] >= 0
    # the verified conservation laws ride the summary for the runtime
    # hook; the package declares at least the cold-door and drop ones
    exprs = {c["expr"] for c in summary["closures"]}
    assert "_cold_evicted == _cold_stored + _cold_dropped" in exprs
    assert "_dropped == _drop_reasons" in exprs


def test_cli_sarif_subprocess():
    out = subprocess.run(
        [sys.executable, "-m", "tools.apexlint", "ape_x_dqn_tpu/",
         "--format=sarif"],
        capture_output=True, text=True, timeout=120, cwd=REPO_ROOT)
    assert out.returncode == 0, out.stdout + out.stderr
    sarif = json.loads(out.stdout)
    assert sarif["version"] == "2.1.0"
    driver = sarif["runs"][0]["tool"]["driver"]
    assert driver["name"] == "apexlint"
    assert {r["id"] for r in driver["rules"]} >= {
        "use-after-donate", "host-sync", "learner-parity",
        "thread-lifecycle", "resource-lifecycle", "counter-closure"}
    # per-rule timing properties (satellite: CI spots a slow checker)
    for r in driver["rules"]:
        assert set(r["properties"]) == {"findings", "waivers", "ms"}
    assert sarif["runs"][0]["results"] == []


def test_cli_changed_only_filters_and_annotates():
    # vs HEAD with a clean tree the package has no changed findings
    # either way (the gate is already zero); the mode must still run
    # the whole-program analysis and annotate the summary
    out = subprocess.run(
        [sys.executable, "-m", "tools.apexlint", "ape_x_dqn_tpu/",
         "--changed-only", "HEAD", "--format=json"],
        capture_output=True, text=True, timeout=120, cwd=REPO_ROOT)
    assert out.returncode == 0, out.stdout + out.stderr
    summary = json.loads(out.stdout)
    assert summary["findings"] == []
    assert summary["changed_only"]["ref"] == "HEAD"
    # analysis stayed whole-program: all files scanned, all checkers ran
    assert summary["checked_files"] > 50
    assert "learner-parity" in summary["per_checker"]


def test_cli_self_dogfood():
    out = subprocess.run(
        [sys.executable, "-m", "tools.apexlint", "--self"],
        capture_output=True, text=True, timeout=120, cwd=REPO_ROOT)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 finding(s)" in out.stdout


def test_cli_self_asserts_chaos_coverage():
    out = subprocess.run(
        [sys.executable, "-m", "tools.apexlint", "--self",
         "--format=json"],
        capture_output=True, text=True, timeout=120, cwd=REPO_ROOT)
    assert out.returncode == 0, out.stdout + out.stderr
    summary = json.loads(out.stdout)
    # the dogfood run must actually sweep the fault injectors — the
    # thread/resource checkers exist for exactly that kind of code
    assert summary["self_scope"]["tools/chaos"] >= 3


def test_cli_text_nonzero_exit_on_findings(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "racy.py").write_text(
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = None\n"
        "        self._n = 0  # guarded-by: _lock\n"
        "    def bump(self):\n"
        "        self._n += 1\n")
    out = subprocess.run(
        [sys.executable, "-m", "tools.apexlint", str(pkg)],
        capture_output=True, text=True, timeout=120, cwd=REPO_ROOT)
    assert out.returncode == 1
    assert "guarded-by" in out.stdout


# -- checker calibration on fixtures --------------------------------------

def test_guarded_by_fixtures():
    good = guarded_by.check_paths([_fx("guarded_good.py")])
    assert good.findings == []
    assert good.waivers == 1  # the justified teardown write

    bad = guarded_by.check_paths([_fx("guarded_bad.py")])
    assert len(bad.findings) == 1
    f = bad.findings[0]
    assert f.checker == "guarded-by"
    assert "self._count" in f.message and "_lock" in f.message
    assert bad.waivers == 1  # the waived closure write


def test_guarded_by_holder_method_counts_as_its_declared_lock(tmp_path):
    """`with self.<method>(...)` holds a lock only when the method's
    def line declares it with `# apexlint: holds(<lock>)`."""
    body = (
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = None\n"
        "        self._n = 0  # guarded-by: _lock\n"
        "    def _hold(self, who):{mark}\n"
        "        return self._lock\n"
        "    def bump(self):\n"
        "        with self._hold('bump'):\n"
        "            self._n += 1\n")
    declared = tmp_path / "declared.py"
    declared.write_text(body.format(mark="  # apexlint: holds(_lock)"))
    assert guarded_by.check_paths([str(declared)]).findings == []
    undeclared = tmp_path / "undeclared.py"
    undeclared.write_text(body.format(mark=""))
    found = guarded_by.check_paths([str(undeclared)]).findings
    assert len(found) == 1 and "self._n" in found[0].message
    wrong = tmp_path / "wrong.py"
    wrong.write_text(body.format(mark="  # apexlint: holds(_other)"))
    assert len(guarded_by.check_paths([str(wrong)]).findings) == 1


def test_jit_purity_fixtures():
    good = jit_purity.check_paths([_fx("jit_good.py")])
    assert good.findings == []
    assert good.waivers == 1  # the justified trace-time print

    bad = jit_purity.check_paths([_fx("jit_bad.py")])
    assert len(bad.findings) == 1
    f = bad.findings[0]
    assert f.checker == "jit-purity"
    assert "time.time" in f.message
    assert "_timed_residual" in f.message  # names the reachable hop


def test_jit_purity_cross_module_fixtures():
    """v2: the jit boundary and the host effect live in DIFFERENT
    modules — the checker must follow `from x import y` through the
    call graph and anchor the finding at the effect's line in the
    helper module."""
    good = jit_purity.check_paths(
        [_fx("xjit_good_entry.py"), _fx("xjit_good_util.py")])
    assert good.findings == []
    assert good.waivers == 0

    bad = jit_purity.check_paths(
        [_fx("xjit_bad_entry.py"), _fx("xjit_bad_util.py")])
    assert len(bad.findings) == 1
    f = bad.findings[0]
    assert f.checker == "jit-purity"
    assert "time.time" in f.message
    assert "residual_scale" in f.message  # names the cross-module hop
    assert f.path.endswith("xjit_bad_util.py")  # anchored at the effect

    # module-local degeneration: the entry file alone cannot see the
    # impurity (the import resolves to nothing and stays opaque)
    alone = jit_purity.check_paths([_fx("xjit_bad_entry.py")])
    assert alone.findings == []


def test_use_after_donate_fixtures():
    good = use_after_donate.check_paths([_fx("donate_good.py")])
    assert good.findings == []
    assert good.waivers == 1  # the audited metadata read

    bad = use_after_donate.check_paths([_fx("donate_bad.py")])
    assert len(bad.findings) == 1
    f = bad.findings[0]
    assert f.checker == "use-after-donate"
    assert "state" in f.message and "train_step" in f.message
    assert "deleted" in f.message


def test_host_sync_fixtures():
    good = host_sync.check_paths([_fx("hostsync_good.py")])
    assert good.findings == []
    assert good.waivers == 1  # the one explicit fused fetch

    bad = host_sync.check_paths([_fx("hostsync_bad.py")])
    assert len(bad.findings) == 1
    f = bad.findings[0]
    assert f.checker == "host-sync"
    assert "float()" in f.message
    assert "learn_loop" in f.message


def test_host_sync_scope_is_opt_in(tmp_path):
    # the same sync OUTSIDE a hot module (no marker, basename not in
    # HOT_BASENAMES) is not flagged: checkpointing and teardown code
    # legitimately syncs
    bad_src = open(_fx("hostsync_bad.py"), encoding="utf-8").read()
    elsewhere = tmp_path / "elsewhere.py"
    elsewhere.write_text(bad_src.replace("# apexlint-scope: hot-path", ""))
    res = host_sync.check_paths([str(elsewhere)])
    assert res.findings == []


def test_config_coverage_fixtures():
    good_dir = _fx("cfgcov_good")
    good_paths = [os.path.join(good_dir, n)
                  for n in ("configs.py", "reader.py")]
    good = config_coverage.check(
        good_paths, readme_path=os.path.join(good_dir, "README.md"))
    assert good.findings == []
    assert good.waivers == 1  # the declared-dormant fault_rate

    bad_dir = _fx("cfgcov_bad")
    bad_paths = [os.path.join(bad_dir, n)
                 for n in ("configs.py", "reader.py")]
    bad = config_coverage.check(
        bad_paths, readme_path=os.path.join(bad_dir, "README.md"))
    msgs = [f.message for f in bad.findings]
    assert any("dead_knob" in m and "read nowhere" in m for m in msgs)
    assert any("phantom_knob" in m and "no field" in m for m in msgs)
    assert len(bad.findings) == 2


def test_learner_parity_fixtures():
    good = learner_parity.check_paths([_fx("parity_good.py")])
    assert good.findings == []
    assert good.waivers == 1  # the declared add() asymmetry

    bad = learner_parity.check_paths([_fx("parity_bad.py")])
    assert len(bad.findings) == 1
    f = bad.findings[0]
    assert f.checker == "learner-parity"
    assert "BetaLearner" in f.message and "add()" in f.message
    assert "AlphaLearner" in f.message  # names who has the endpoint


def test_learner_parity_waiver_must_name_endpoint(tmp_path):
    # a parity waiver that does not MENTION the drifted endpoint does
    # not absorb the finding — blanket waivers can't hide future drift
    src = open(_fx("parity_good.py"), encoding="utf-8").read()
    blanket = tmp_path / "parity_blanket.py"
    blanket.write_text(src.replace(
        "parity(no add — beta ingests through alpha's staging ring)",
        "parity(beta is special)"))
    res = learner_parity.check_paths([str(blanket)])
    assert len(res.findings) == 1
    assert "add()" in res.findings[0].message


def test_wire_protocol_fixtures():
    good = wire_protocol.check_paths([_fx("wire_good.py")])
    assert good.findings == []
    assert good.waivers == 2  # MSG_LEGACY waived in both chains

    bad = wire_protocol.check_paths([_fx("wire_bad.py")])
    assert len(bad.findings) == 1
    f = bad.findings[0]
    assert f.checker == "wire-protocol"
    assert "MSG_PONG" in f.message and "Server" in f.message


def test_wire_protocol_telemetry_fixtures():
    good = wire_protocol.check_paths([_fx("wire_telemetry_good.py")])
    assert good.findings == []
    assert good.waivers == 0  # fully wired, nothing to excuse

    bad = wire_protocol.check_paths([_fx("wire_telemetry_bad.py")])
    assert len(bad.findings) == 1
    f = bad.findings[0]
    assert f.checker == "wire-protocol"
    assert "MSG_TELEMETRY" in f.message and "Server" in f.message


def test_wire_protocol_push_fixtures():
    good = wire_protocol.check_paths([_fx("wire_push_good.py")])
    assert good.findings == []
    assert good.waivers == 0  # push wired into both chains

    bad = wire_protocol.check_paths([_fx("wire_push_bad.py")])
    assert len(bad.findings) == 1
    f = bad.findings[0]
    assert f.checker == "wire-protocol"
    assert "MSG_PARAMS_PUSH" in f.message and "Client" in f.message


def test_wire_protocol_shm_fixtures():
    # ISSUE 18: the doorbell frame rides the SAME dispatch chains as
    # every other MSG_* — a server that grants rings but a client that
    # never posts doorbells is the half-wired state the checker exists
    # to catch
    good = wire_protocol.check_paths([_fx("wire_shm_good.py")])
    assert good.findings == []
    assert good.waivers == 0  # doorbell wired into both chains

    bad = wire_protocol.check_paths([_fx("wire_shm_bad.py")])
    assert len(bad.findings) == 1
    f = bad.findings[0]
    assert f.checker == "wire-protocol"
    assert "MSG_SHM_DOORBELL" in f.message and "Client" in f.message


def test_wire_protocol_paramtag_fixtures():
    # ISSUE 19: the param payload TAG ('APXV' raw-versioned vs 'APXC'
    # delta-coded) is a protocol family one level below MSG_* — a
    # parser sniffing one tag while the publisher ships both stalls
    # exactly the peers that negotiated the codec. The bad fixture
    # also IMPORTS its tags (the real split: tags in param_codec.py,
    # parser in socket_transport.py), so it calibrates that imported
    # names count toward the module's tag family.
    good = wire_protocol.check_paths([_fx("wire_paramtag_good.py")])
    assert good.findings == []
    assert good.waivers == 0  # both tags routed, nothing to excuse

    bad = wire_protocol.check_paths([_fx("wire_paramtag_bad.py")])
    assert len(bad.findings) == 1
    f = bad.findings[0]
    assert f.checker == "wire-protocol"
    assert "PARAMS_CODEC_MAGIC" in f.message and "Parser" in f.message
    assert "payload-tag" in f.message


def test_retry_annotation_fixtures():
    good = retry_annotation.check_paths(
        [_fx(os.path.join("comm", "retry_good.py"))])
    assert good.findings == []
    assert good.waivers == 1  # the justified close-path waiver

    bad = retry_annotation.check_paths(
        [_fx(os.path.join("comm", "retry_bad.py"))])
    assert len(bad.findings) == 1
    f = bad.findings[0]
    assert f.checker == "retry-annotation"
    assert "OSError" in f.message and "lossy" in f.message


def test_retry_annotation_replay_fixtures():
    # PR 16 extends the checker's scope to replay/ — the disk spill
    # rung does real file IO and a swallowed OSError there is a
    # silently lost segment
    good = retry_annotation.check_paths(
        [_fx(os.path.join("replay", "diskio_good.py"))])
    assert good.findings == []
    assert good.waivers == 1  # the justified shutdown-close waiver

    bad = retry_annotation.check_paths(
        [_fx(os.path.join("replay", "diskio_bad.py"))])
    assert len(bad.findings) == 1
    f = bad.findings[0]
    assert f.checker == "retry-annotation"
    assert "OSError" in f.message and "lossy" in f.message


def test_retry_annotation_scope_is_comm_runtime_replay(tmp_path):
    # the same silent swallow OUTSIDE comm/, runtime/, or replay/ is
    # not flagged: the rule is about the transport/runtime/spill loss
    # contract, not a repo-wide style ban
    bad_src = open(
        _fx(os.path.join("comm", "retry_bad.py")), encoding="utf-8").read()
    elsewhere = tmp_path / "elsewhere.py"
    elsewhere.write_text(bad_src)
    res = retry_annotation.check_paths([str(elsewhere)])
    assert res.findings == []


def test_remediation_accounting_fixtures():
    good = remediation_accounting.check_paths(
        [_fx(os.path.join("runtime", "remediation_good.py"))])
    assert good.findings == []
    assert good.waivers == 1  # the justified central-dispatch waiver

    bad = remediation_accounting.check_paths(
        [_fx(os.path.join("runtime", "remediation_bad.py"))])
    assert len(bad.findings) == 1
    f = bad.findings[0]
    assert f.checker == "remediation-accounting"
    assert "quarantine_peer" in f.message and "unaccounted" in f.message


def test_remediation_accounting_scope_is_runtime(tmp_path):
    # an uncounted actuator call OUTSIDE runtime/ is not flagged: the
    # rule enforces the remediation plane's audit-trail contract, not a
    # repo-wide naming ban (a harness may wire bare actuators on purpose)
    bad_src = open(
        _fx(os.path.join("runtime", "remediation_bad.py")),
        encoding="utf-8").read()
    elsewhere = tmp_path / "elsewhere.py"
    elsewhere.write_text(bad_src)
    res = remediation_accounting.check_paths([str(elsewhere)])
    assert res.findings == []


def test_obs_names_fixtures():
    report = _fx("obs_report_fixture.py")
    good = obs_names.check([_fx("obs_good.py")], report)
    # dead_row is listed-but-unemitted even against the good emitter
    assert [f for f in good.findings if "dead_row" not in f.message] == []
    assert good.waivers == 2  # scratch_gauge emission + external_row row

    bad = obs_names.check([_fx("obs_good.py"), _fx("obs_bad.py")], report)
    msgs = [f.message for f in bad.findings]
    assert any("rogue_counter" in m for m in msgs)
    assert any("dead_row" in m for m in msgs)
    assert len(bad.findings) == 2


def test_obs_names_profiling_fixtures():
    """The perf-plane fixture pair (ISSUE 8): the good emitter's
    literal if/elif stage gauges + compile counters cross-reference
    cleanly; the bad emitter drifts both ways (kind mismatch on an
    existing row, a brand-new gauge with no row)."""
    report = _fx("profiling_report_fixture.py")
    good = obs_names.check([_fx("profiling_good.py")], report)
    assert good.findings == []
    assert good.waivers == 0

    bad = obs_names.check(
        [_fx("profiling_good.py"), _fx("profiling_bad.py")], report)
    msgs = [f.message for f in bad.findings]
    assert any("mfu_learn_k" in m for m in msgs)  # gauge-vs-ctr drift
    assert any("mfu_scratch" in m for m in msgs)  # unlisted emission
    assert len(bad.findings) == 2


def test_obs_names_learning_fixtures():
    """The learning-plane fixture pair (ISSUE 10): the good emitter's
    publish_learn literal gauges + loss histogram + degradation counter
    cross-reference cleanly (tenant-prefixed f-string keys invisible by
    design); the bad emitter drifts both ways (grad_norm emitted as a
    counter, an unlisted diagnostic gauge)."""
    report = _fx("learning_report_fixture.py")
    good = obs_names.check([_fx("learning_good.py")], report)
    assert good.findings == []
    assert good.waivers == 0

    bad = obs_names.check(
        [_fx("learning_good.py"), _fx("learning_bad.py")], report)
    msgs = [f.message for f in bad.findings]
    assert any("learn_grad_norm" in m for m in msgs)  # gauge-vs-ctr
    assert any("learn_scratch_frac" in m for m in msgs)  # unlisted
    assert len(bad.findings) == 2


def test_obs_names_multichip_fixtures():
    """The dp-scaling fixture pair (ISSUE 9): the good emitter's
    publish_multichip + train_dist literal gauges cross-reference
    cleanly against the mini table; the bad emitter drifts both ways
    (efficiency emitted as a counter, an unlisted per-shard gauge)."""
    report = _fx("multichip_report_fixture.py")
    good = obs_names.check([_fx("multichip_good.py")], report)
    assert good.findings == []
    assert good.waivers == 0

    bad = obs_names.check(
        [_fx("multichip_good.py"), _fx("multichip_bad.py")], report)
    msgs = [f.message for f in bad.findings]
    assert any("dp_scaling_efficiency" in m for m in msgs)
    assert any("replay_shard_fill_median" in m for m in msgs)
    assert len(bad.findings) == 2


def test_obs_names_cold_fixtures():
    """The cold-tier fixture pair (ISSUE 11): the good emitter's
    occupancy/ratio gauges + eviction/recall counters cross-reference
    cleanly against the mini table; the bad emitter drifts both ways
    (the ratio emitted as a counter, an unlisted recall-lag gauge)."""
    report = _fx("cold_report_fixture.py")
    good = obs_names.check([_fx("cold_good.py")], report)
    assert good.findings == []
    assert good.waivers == 0

    bad = obs_names.check(
        [_fx("cold_good.py"), _fx("cold_bad.py")], report)
    msgs = [f.message for f in bad.findings]
    assert any("cold_compression_ratio" in m for m in msgs)  # kind
    assert any("cold_recall_lag_s" in m for m in msgs)  # unlisted
    assert len(bad.findings) == 2


def test_obs_names_serve_fixtures():
    """The serving-tier fixture pair (ISSUE 13): the good emitter's
    admission counters + tier gauges + latency histogram
    cross-reference cleanly (per-tenant serve/<tenant>/ f-string keys
    invisible by design); the bad emitter drifts both ways (queue
    depth emitted as a counter, an unlisted admission-outcome
    counter)."""
    report = _fx("serve_report_fixture.py")
    good = obs_names.check([_fx("serve_good.py")], report)
    assert good.findings == []
    assert good.waivers == 0

    bad = obs_names.check(
        [_fx("serve_good.py"), _fx("serve_bad.py")], report)
    msgs = [f.message for f in bad.findings]
    assert any("serve_queue_items" in m for m in msgs)  # gauge-vs-ctr
    assert any("serve_preempted" in m for m in msgs)  # unlisted
    assert len(bad.findings) == 2


def test_obs_names_blackbox_fixtures():
    """The forensics fixture pair (ISSUE 17): the good emitter's
    ring/dump/bundle counters cross-reference cleanly against the
    mini table; the bad emitter drifts both ways (dumps emitted as a
    gauge, an unlisted scratch counter)."""
    report = _fx("blackbox_report_fixture.py")
    good = obs_names.check([_fx("blackbox_good.py")], report)
    assert good.findings == []
    assert good.waivers == 0

    bad = obs_names.check(
        [_fx("blackbox_good.py"), _fx("blackbox_bad.py")], report)
    msgs = [f.message for f in bad.findings]
    assert any("blackbox_dumps" in m for m in msgs)  # ctr-vs-gauge
    assert any("blackbox_scratch" in m for m in msgs)  # unlisted
    assert len(bad.findings) == 2


def test_config_coverage_serving_scope(tmp_path):
    """ServingConfig is in the README-knob scope (ISSUE 13): a README
    naming a nonexistent serving.<knob> fails, a real knob passes, and
    an unread ServingConfig field fails direction 1."""
    from tools.apexlint import config_coverage

    configs = tmp_path / "configs.py"
    configs.write_text(
        "from dataclasses import dataclass\n"
        "@dataclass\nclass ServingConfig:\n"
        "    multi_tenant: bool = False\n"
        "    dead_knob: int = 0\n")
    reader = tmp_path / "reader.py"
    reader.write_text("def f(cfg):\n    return cfg.multi_tenant\n")
    readme = tmp_path / "README.md"
    readme.write_text("set serving.multi_tenant, not "
                      "serving.imaginary_knob\n")
    res = config_coverage.check(
        [str(configs), str(reader)], configs_path=str(configs),
        readme_path=str(readme))
    msgs = [f.message for f in res.findings]
    assert any("serving.imaginary_knob" in m for m in msgs)
    assert any("ServingConfig.dead_knob" in m for m in msgs)
    assert not any("multi_tenant" in m for m in msgs)
    assert len(res.findings) == 2


def test_config_coverage_learns_a_new_block_from_network_config(tmp_path):
    """A decoder's block is a field of NetworkConfig whose annotation
    names a dataclass of configs.py: added to a copy of the package's
    own configs.py, its knobs are in the README-knob scope and the
    block itself is no dead knob, with no edit to the checker."""
    src = open(os.path.join(REPO_ROOT, "ape_x_dqn_tpu", "configs.py"),
               encoding="utf-8").read()
    head = "@dataclass(frozen=True)\nclass NetworkConfig:\n"
    assert src.count(head) == 1
    configs = tmp_path / "configs.py"
    configs.write_text(src.replace(
        head,
        "@dataclass(frozen=True)\nclass NinthConfig:\n"
        "    width: int = 8\n    dead_knob: int = 0\n\n\n" + head
        + "    ninth: NinthConfig = field(default_factory=NinthConfig)\n"))
    reader = tmp_path / "reader.py"
    reader.write_text("def f(block):\n    return block.width\n")
    readme = tmp_path / "README.md"
    readme.write_text("set network.ninth.width, not ninth.imaginary; "
                      "network.jamba.vocab_size stays a knob\n")
    res = config_coverage.check(
        [str(configs), str(reader)], configs_path=str(configs),
        readme_path=str(readme))
    ours = [f.message for f in res.findings if "inth" in f.message]
    assert any("ninth.imaginary" in m and "NinthConfig" in m for m in ours)
    assert any("NinthConfig.dead_knob" in m for m in ours)
    assert len(ours) == 2, ours
    assert not any("jamba.vocab_size" in f.message for f in res.findings)
    # no net's name in the checker
    checker = open(config_coverage.__file__, encoding="utf-8").read()
    assert not any(block in checker for block in (
        "glm", "afmoe", "smallthinker", "ouro", "kimi", "lfm2", "minicpm",
        "jamba"))


def test_config_coverage_param_codec_scope(tmp_path):
    """ISSUE 19 knobs stay in scope: `comm.param_codec` read through
    getattr counts as a read (train.py reads the codec knobs exactly
    that way, for configs checkpointed before the field existed), a
    dead param_* knob still flags, and a README naming a nonexistent
    comm.param_* knob flags the phantom direction."""
    from tools.apexlint import config_coverage

    configs = tmp_path / "configs.py"
    configs.write_text(
        "from dataclasses import dataclass\n"
        "@dataclass\nclass CommConfig:\n"
        "    param_codec: str = 'delta-q8'\n"
        "    param_delta_window: int = 8\n"
        "    param_dead_knob: int = 0\n")
    reader = tmp_path / "reader.py"
    reader.write_text(
        "def f(cfg):\n"
        "    codec = getattr(cfg, 'param_codec', 'raw')\n"
        "    return codec, cfg.param_delta_window\n")
    readme = tmp_path / "README.md"
    readme.write_text(
        "set comm.param_codec and comm.param_delta_window, "
        "not comm.param_phantom_knob\n")
    res = config_coverage.check(
        [str(configs), str(reader)], configs_path=str(configs),
        readme_path=str(readme))
    msgs = [f.message for f in res.findings]
    assert any("comm.param_phantom_knob" in m for m in msgs)
    assert any("CommConfig.param_dead_knob" in m for m in msgs)
    assert not any("param_codec" in m or "param_delta_window" in m
                   for m in msgs)
    assert len(res.findings) == 2


def test_obs_names_kind_mismatch(tmp_path):
    emit = tmp_path / "emit.py"
    emit.write_text("def f(obs):\n    obs.gauge('x_name', 1)\n")
    report = tmp_path / "report.py"
    report.write_text("INSTRUMENTS = {'x_name': {'kind': 'ctr'}}\n")
    res = obs_names.check([str(emit)], str(report))
    assert len(res.findings) == 1
    assert "listed as ctr but emitted as gauge" in res.findings[0].message


# -- v3 checker calibration (thread/resource lifecycle, closures) ---------

def test_thread_lifecycle_fixtures():
    good = thread_lifecycle.check_paths([_fx("thread_good.py")])
    assert good.findings == []
    assert good.waivers == 1  # the deliberately detached reader

    bad = thread_lifecycle.check_paths(
        [_fx("thread_unbounded_join_bad.py")])
    assert len(bad.findings) == 1
    f = bad.findings[0]
    assert f.checker == "thread-lifecycle"
    assert "unbounded .join()" in f.message
    assert f.line == 22  # the join line, not the construction


def test_thread_lifecycle_stopflag_fixture():
    bad = thread_lifecycle.check_paths([_fx("thread_stopflag_bad.py")])
    assert len(bad.findings) == 1
    f = bad.findings[0]
    assert f.checker == "thread-lifecycle"
    assert "never consults a stop signal" in f.message


def test_thread_lifecycle_fireforget_fixture():
    bad = thread_lifecycle.check_paths(
        [_fx("thread_fireforget_bad.py")])
    assert len(bad.findings) == 1
    f = bad.findings[0]
    assert f.checker == "thread-lifecycle"
    assert "fire-and-forget" in f.message


def test_resource_lifecycle_fixtures():
    good = resource_lifecycle.check_paths([_fx("resource_good.py")])
    assert good.findings == []
    assert good.waivers == 1  # the caller-owned socket

    bad = resource_lifecycle.check_paths([_fx("resource_order_bad.py")])
    assert len(bad.findings) == 1
    f = bad.findings[0]
    assert f.checker == "resource-lifecycle"
    assert "out of declared order" in f.message
    assert "close() runs before unlink()" in f.message
    assert "PR 18" in f.message


def test_resource_lifecycle_leak_fixture():
    bad = resource_lifecycle.check_paths([_fx("resource_leak_bad.py")])
    assert len(bad.findings) == 1
    f = bad.findings[0]
    assert f.checker == "resource-lifecycle"
    assert "defines no teardown method" in f.message


def test_counter_closure_fixtures():
    good = counter_closure.check_paths([_fx("closure_good.py")])
    assert good.findings == []
    assert good.waivers == 1  # the rebalance move outside the law

    bad = counter_closure.check_paths([_fx("closure_leak_bad.py")])
    assert len(bad.findings) == 1
    f = bad.findings[0]
    assert f.checker == "counter-closure"
    assert "a path leaks (0 term bumps)" in f.message
    assert f.line == 17  # the _evicted bump whose error path leaks


def test_counter_closure_runtime_hook():
    decls = counter_closure.declarations([_fx("closure_good.py")])
    assert [d["expr"] for d in decls] == \
        ["_evicted == _stored + _dropped"]
    decl = decls[0]

    class Ledger:
        pass

    obj = Ledger()
    obj._evicted, obj._stored, obj._dropped = 5, 3, 2
    counter_closure.check_object(obj, decl)  # holds: silent
    obj._dropped = {"reset": 1, "timeout": 0}  # dict terms sum
    obj._evicted = 4
    counter_closure.check_object(obj, decl)
    obj._evicted = 9
    with pytest.raises(AssertionError) as ei:
        counter_closure.check_object(obj, decl)
    assert "_evicted == _stored + _dropped" in str(ei.value)


def test_v3_fixed_modules_stay_clean():
    """Regression pins for the real findings the seeding sweep fixed:
    the unbounded actor join + fire-and-forget bp watchdog
    (runtime/actor_host.py), the never-joined stall watchdog
    (obs/health.py), the undrained ingest queue
    (comm/socket_transport.py), and the teardown-less loopback queue
    (comm/transport.py). Single-file re-lints keep each fix honest
    even if the package-wide gate's scope ever changes."""
    pkg = os.path.join(REPO_ROOT, "ape_x_dqn_tpu")
    for rel in ("runtime/actor_host.py", "obs/health.py"):
        res = thread_lifecycle.check_paths([os.path.join(pkg, rel)])
        assert res.findings == [], (rel, [str(f) for f in res.findings])
    for rel in ("comm/socket_transport.py", "comm/transport.py"):
        res = resource_lifecycle.check_paths([os.path.join(pkg, rel)])
        assert res.findings == [], (rel, [str(f) for f in res.findings])


# -- lock-order witness ---------------------------------------------------

def _witness_pair():
    from ape_x_dqn_tpu.obs.health import LockOrderRecorder, WitnessLock
    rec = LockOrderRecorder()
    return (WitnessLock("A", rec), WitnessLock("B", rec),
            WitnessLock("C", rec))


def test_lock_order_cycle_raises():
    from ape_x_dqn_tpu.obs.health import LockOrderError
    a, b, _ = _witness_pair()
    with a:
        with b:
            pass
    with pytest.raises(LockOrderError) as ei:
        with b:
            with a:  # pragma: no cover - raises before entering
                pass
    assert "'A'" in str(ei.value) and "'B'" in str(ei.value)


def test_lock_order_transitive_cycle_raises():
    from ape_x_dqn_tpu.obs.health import LockOrderError
    a, b, c = _witness_pair()
    with a, b:
        pass
    with b, c:
        pass
    with pytest.raises(LockOrderError):
        with c, a:
            pass


def test_lock_order_consistent_is_silent():
    a, b, c = _witness_pair()
    for _ in range(3):
        with a, b, c:
            pass
        with a, c:
            pass
        with b, c:
            pass


def test_lock_order_same_name_self_edge_ignored():
    from ape_x_dqn_tpu.obs.health import LockOrderRecorder, WitnessLock
    rec = LockOrderRecorder()
    x1 = WitnessLock("leaf", rec)
    x2 = WitnessLock("leaf", rec)
    with x1:
        with x2:  # distinct instances, shared name: no self-edge
            pass


def test_make_lock_is_witness_under_tests():
    # conftest sets APEX_LOCK_WITNESS=1 before any package import
    from ape_x_dqn_tpu.obs.health import WitnessLock, make_lock
    lock = make_lock("test.lock")
    assert isinstance(lock, WitnessLock)
    with lock:
        assert lock.locked()
    assert not lock.locked()


def test_witness_acquire_release_api():
    from ape_x_dqn_tpu.obs.health import LockOrderRecorder, WitnessLock
    rec = LockOrderRecorder()
    lock = WitnessLock("api", rec)
    assert lock.acquire(blocking=False)
    assert not lock.acquire(blocking=False)  # non-reentrant, held
    lock.release()
    assert not lock.locked()
