"""apexlint: project-native static analysis for the Ape-X runtime.

Thirteen stdlib-only AST checkers over the package source (no imports
of the code under analysis, no third-party deps). The v1 five are
single-file passes; v2 added a shared cross-module call graph
(callgraph.py) and whole-program dataflow checkers; v3 adds
thread/resource lifecycle analysis and statically-enforced accounting
closures on the same graph:

- guarded-by       lock discipline for `# guarded-by: <lock>` attrs
- jit-purity       no host effects reachable from jax.jit boundaries
                   (package-wide reachability through imports, module
                   aliases, and cross-module inheritance)
- wire-protocol    every MSG_* handled in every dispatch chain
- obs-names        emitted instruments <-> obs/report.py table
- retry-annotation swallowed socket errors in comm/runtime must emit
                   an accounting bump or carry `# apexlint: lossy(...)`
- remediation-accounting
                   every fleet-actuator call site in runtime/ bumps a
                   remediation_* counter or carries
                   `# apexlint: unaccounted(...)`
- use-after-donate no reads of a buffer after it was donated to a
                   `donate_argnums` jit without an intervening rebind
- host-sync        no hidden `.item()`/`np.asarray`/`float()`/
                   `block_until_ready` device syncs in the hot-path
                   modules outside obs windows
- config-coverage  every configs.py dataclass field is read somewhere;
                   every README `replay./comm./obs./actors.` knob exists
- learner-parity   the four learner variants' jitted endpoint surfaces
                   (names, donation pattern, metrics["diag"] threading)
                   stay in lockstep (ROADMAP item 5's enforcement)
- thread-lifecycle every threading.Thread is retained, its target
                   consults a stop signal, and teardown reaches a
                   bounded join(timeout=...) — unbounded joins and
                   fire-and-forget threads are findings
                   (`# apexlint: detached(reason)` waives)
- resource-lifecycle
                   SharedMemory / file / socket / bounded-queue
                   acquires stored on self have a release reachable
                   from teardown, with declarable ordering
                   (`# apexlint: releases(_seg, unlink<close)` —
                   the PR 18 close-pins-mapping class)
- counter-closure  conservation laws declared at the counter-owning
                   class (`# apexlint: closure(evicted == stored +
                   dropped)`) verified at every LHS increment site by
                   suffix post-dominance; declarations double as a
                   debug-mode runtime assertion feed
                   (counter_closure.check_object)

CLI: `python -m tools.apexlint ape_x_dqn_tpu/ [--format=json|sarif]
[--changed-only <git-ref>] [--self]` exits 0 only with zero unwaived
findings; tests/test_apexlint.py runs it over the package as a tier-1
gate, and `--self` dogfoods the structural checkers on tools/ itself.
The dynamic companion (the lock-order witness) lives in
ape_x_dqn_tpu/obs/health.py, enabled under APEX_LOCK_WITNESS=1 by
tests/conftest.py.
"""

from __future__ import annotations

import os
import time

from tools.apexlint import (
    config_coverage, counter_closure, guarded_by, host_sync, jit_purity,
    learner_parity, obs_names, remediation_accounting, resource_lifecycle,
    retry_annotation, thread_lifecycle, use_after_donate, wire_protocol)
from tools.apexlint.common import CheckResult, Finding, ModuleSource

__all__ = ["CheckResult", "Finding", "ModuleSource", "run",
           "package_files"]


def package_files(package_dir: str) -> list[str]:
    out: list[str] = []
    for root, dirs, files in os.walk(package_dir):
        dirs[:] = sorted(d for d in dirs if d != "__pycache__")
        for name in sorted(files):
            if name.endswith(".py"):
                out.append(os.path.join(root, name))
    return out


def run(package_dir: str,
        report_path: str | None = None,
        readme_path: str | None = None) -> dict:
    """Run all checkers over a package tree; returns the JSON-shaped
    summary the CLI and tests/test_apexlint.py consume.

    per_checker maps each checker to {"findings": n, "waivers": n,
    "ms": wall-clock} so waiver creep AND a checker gone slow are both
    attributable per rule in that summary; top-level
    `findings`/`waivers` stay the aggregate view. `closures` lists the
    counter-closure declarations the static pass verified — the
    debug-mode runtime hook (counter_closure.check_object) asserts the
    same laws on live objects.
    """
    paths = package_files(package_dir)
    total = CheckResult()
    per_checker: dict[str, dict[str, float]] = {}

    def fold(name: str, check) -> None:
        t0 = time.perf_counter()
        res = check()
        per_checker[name] = {"findings": len(res.findings),
                             "waivers": res.waivers,
                             "ms": round(
                                 (time.perf_counter() - t0) * 1e3, 2)}
        total.merge(res)

    fold("guarded-by", lambda: guarded_by.check_paths(paths))
    fold("jit-purity", lambda: jit_purity.check_paths(paths))
    fold("wire-protocol", lambda: wire_protocol.check_paths(paths))
    fold("retry-annotation",
         lambda: retry_annotation.check_paths(paths))
    fold("remediation-accounting",
         lambda: remediation_accounting.check_paths(paths))
    fold("use-after-donate",
         lambda: use_after_donate.check_paths(paths))
    fold("host-sync", lambda: host_sync.check_paths(paths))
    fold("learner-parity", lambda: learner_parity.check_paths(paths))
    fold("thread-lifecycle",
         lambda: thread_lifecycle.check_paths(paths))
    fold("resource-lifecycle",
         lambda: resource_lifecycle.check_paths(paths))
    fold("counter-closure",
         lambda: counter_closure.check_paths(paths))
    if readme_path is None:
        candidate = os.path.join(
            os.path.dirname(os.path.abspath(package_dir.rstrip(os.sep))),
            "README.md")
        readme_path = candidate if os.path.exists(candidate) else None
    fold("config-coverage",
         lambda: config_coverage.check(paths, readme_path=readme_path))
    if report_path is None:
        candidate = os.path.join(package_dir, "obs", "report.py")
        report_path = candidate if os.path.exists(candidate) else None
    if report_path is not None:
        fold("obs-names", lambda: obs_names.check(paths, report_path))
    return {
        "findings": [f.as_dict() for f in total.findings],
        "waivers": total.waivers,
        "per_checker": per_checker,
        "checked_files": len(paths),
        "closures": counter_closure.declarations(paths),
    }
