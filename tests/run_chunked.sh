#!/usr/bin/env bash
# Chunked test-suite runner: one pytest process per test file.
#
# Why: one long-lived `pytest tests/` process has reproducibly SIGSEGVed
# at ~85% inside XLA's backend_compile_and_load — an accumulation crash
# in the XLA CPU client, not a test failure. Running each file in its
# own interpreter bounds per-process compile-cache growth and makes the
# full suite (including -m slow, if you drop the filter) completable in
# one command. The gate every PR is held to is the driver's one pytest
# command over six xdist workers (ROADMAP.md, "Tests"); this script is
# the local per-file convenience, and it judges no rate: speed is
# measured on the chip by benchmarks/run.py (BENCHMARK.json).
#
# Usage:
#   tests/run_chunked.sh                 # tier-1 scope, per-file
#   tests/run_chunked.sh -m ''           # include slow tests
#   tests/run_chunked.sh -k kbatch       # extra pytest args pass through
set -u
cd "$(dirname "$0")/.."

fail=0
failed_files=()

# Compile-telemetry ledger (obs/profiling.py): each pytest process
# appends one JSON line {argv, jit_compiles, jit_compile_ms} at exit,
# making the per-file compile-cache growth this chunking exists to
# bound a printed, monitored quantity instead of folklore.
compile_log="$(mktemp "${TMPDIR:-/tmp}/apex_compile_log.XXXXXX")"
export APEX_COMPILE_LOG="${compile_log}"

# Static-analysis gate first: cheap (stdlib-only, no jax import) and a
# finding here usually explains the test failure that would follow.
# The JSON is piped through a per_checker key assertion so a refactor
# that silently drops a checker (v3's lifecycle/closure three
# included) fails HERE, not in a review months later.
echo "=== tools/apexlint"
lint_json="$(python -m tools.apexlint ape_x_dqn_tpu/ --format=json)"
lint_rc=$?
printf '%s\n' "${lint_json}"
if [ "${lint_rc}" -ne 0 ] || ! printf '%s' "${lint_json}" | python -c '
import json, sys
summary = json.load(sys.stdin)
required = {"guarded-by", "jit-purity", "wire-protocol", "obs-names",
            "retry-annotation", "remediation-accounting",
            "use-after-donate", "host-sync", "config-coverage",
            "learner-parity", "thread-lifecycle", "resource-lifecycle",
            "counter-closure"}
missing = required - set(summary["per_checker"])
if missing:
    sys.exit(f"apexlint checkers missing from run: {sorted(missing)}")
'; then
    fail=1
    failed_files+=("tools/apexlint")
fi
echo
for f in tests/test_*.py; do
    echo "=== ${f}"
    lines_before=$(wc -l < "${compile_log}" 2>/dev/null || echo 0)
    if ! env JAX_PLATFORMS=cpu python -m pytest "${f}" -q -m 'not slow' \
        -p no:cacheprovider -p no:xdist -p no:randomly "$@"; then
        fail=1
        failed_files+=("${f}")
    fi
    # crash-safe: only lines this file's process appended (a SIGSEGV
    # before atexit simply prints nothing here)
    tail -n +"$((lines_before + 1))" "${compile_log}" 2>/dev/null \
        | sed 's/^/    compile growth: /'
done

echo
if [ "${fail}" -ne 0 ]; then
    echo "FAILED files: ${failed_files[*]}"
else
    echo "all files passed"
fi
exit "${fail}"
