#!/usr/bin/env bash
# Chunked test-suite runner: one pytest process per test file.
#
# Why: the documented one-command `pytest tests/` invocation
# has reproducibly SIGSEGVed at ~85% inside XLA's
# backend_compile_and_load — an accumulation crash in the long-lived
# XLA CPU client, not a test failure. Running each file in
# its own interpreter bounds per-process compile-cache growth and makes
# the full tier-2 suite (including -m slow, if you drop the filter)
# completable in one command. The tier-1 command in ROADMAP.md stays
# authoritative for CI gating; this script is the local full-suite
# convenience.
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

# Perf-regression gate: the smoke bench compares against the last
# committed BENCH_SMOKE.json artifact and exits nonzero on a >30%
# throughput drop — warn-only gauges above, a hard gate here.
echo
echo "=== bench.py --perf-gate --smoke"
if ! python bench.py --perf-gate --smoke; then
    fail=1
    failed_files+=("bench.py --perf-gate --smoke")
fi

# Learning-health smoke: two short synthetic-Atari tenants through the
# single-process driver with obs on, then the report's --check mode
# gates the published learn_* gauges against the INSTRUMENTS
# healthy-range rows. The lane itself is warn-only (exit 0 as long as
# the plane publishes); --check is where health becomes a hard gate.
echo
echo "=== bench.py --learn-health --smoke"
if ! python bench.py --learn-health --smoke; then
    fail=1
    failed_files+=("bench.py --learn-health --smoke")
elif ! python -m ape_x_dqn_tpu.obs.report LEARN_HEALTH_SMOKE.jsonl --check; then
    fail=1
    failed_files+=("obs.report LEARN_HEALTH_SMOKE.jsonl --check")
fi

# Multi-chip smoke: dp=1,2 over virtual devices, asked for by name
# (the lane then provisions --xla_force_host_platform_device_count in
# its child processes; without "virtual:" it insists on real devices). Proves the sharded ingest/train path end-to-end and
# anti-ratchets dp-scaling efficiency against the last comparable
# (same dp set, same device mode) MULTICHIP_SMOKE.json — incomparable
# baselines are skipped, never compared across shapes.
echo
echo "=== bench.py --multichip virtual:dp=1,2 --smoke"
if ! python bench.py --multichip virtual:dp=1,2 --smoke --perf-gate; then
    fail=1
    failed_files+=("bench.py --multichip virtual:dp=1,2 --smoke")
fi

# Tiered-replay smoke: the eviction-swap A/B + capacity soak
# (replay/cold_store.py). The lane's own criteria (cold tier holds 8x
# the ring at < 1/8 of its bytes/transition) are hard, and --perf-gate
# anti-ratchets the on-arm grad-steps/s against the last comparable
# (same storage/capacity/smoke class) TIERED_SMOKE.json; failing runs
# never reseed the baseline.
echo
echo "=== bench.py --tiered-ab --smoke"
if ! python bench.py --tiered-ab --smoke --perf-gate; then
    fail=1
    failed_files+=("bench.py --tiered-ab --smoke")
fi

# Disk-arm smoke (replay/disk_store.py, PR 16): the same swap loop
# with admission-door losers spilling to the async disk writeback vs
# spill off, plus the retention soak (disk holds 8x the cold tier's
# capacity) and promote() readback. Hard criteria: retention >= 8x,
# zero io_errors/corrupt segments; --perf-gate anti-ratchets the
# on-arm grad-steps/s against the last comparable (same storage/ring/
# cold capacity/smoke class) TIERED_DISK_SMOKE.json; failing runs
# never reseed the baseline.
echo
echo "=== bench.py --tiered-ab --tiered-disk --smoke"
if ! python bench.py --tiered-ab --tiered-disk --smoke --perf-gate; then
    fail=1
    failed_files+=("bench.py --tiered-ab --tiered-disk --smoke")
fi

# Serving-tier smoke: the multi-tenant A/B + 2x-overload shedding
# phase (parallel/inference_server.py serving tier). The lane's own
# criteria are hard (multi/single >= 0.9 both orders pooled, top-class
# p99 inside the INSTRUMENTS healthy range, class-0 shed == 0,
# accounting closure), and --perf-gate anti-ratchets aggregate
# forwards/s against the last comparable (same tenants/max_batch/
# vector/smoke class) SERVE_SMOKE.json; failing runs never reseed.
echo
echo "=== bench.py --serve-ab --smoke"
if ! python bench.py --serve-ab --smoke --perf-gate; then
    fail=1
    failed_files+=("bench.py --serve-ab --smoke")
fi

# Shared-memory transport smoke: the same-host shm ring + doorbell
# plane vs plain TCP loopback (comm/shm_transport.py, ISSUE 18), both
# orders, uncapped + contended (3-producer) arms. The lane's own
# criteria are hard (shm >= 2x TCP contended items/s in BOTH orders,
# slot/drop accounting closed, zero torn slots delivered), and
# --perf-gate anti-ratchets contended shm items/s against the last
# comparable (same producers/units-per-msg/smoke class) SHM_SMOKE.json;
# failing runs never reseed the baseline.
echo
echo "=== bench.py --shm-ab --smoke"
if ! python bench.py --shm-ab --smoke --perf-gate; then
    fail=1
    failed_files+=("bench.py --shm-ab --smoke")
fi

# Param-plane codec smoke: delta-q8 vs raw weight broadcast to real
# push subscribers (comm/param_codec.py, ISSUE 19), both orders, plus
# the capped-link run, the quantized-policy greedy-parity smoke and
# the slow-subscriber isolation arm. The lane's own criteria are hard
# (>= 3x bytes/publish cut in BOTH orders, parity >= 0.99, healthy
# peers unmoved by a wedged one), and --perf-gate anti-ratchets the
# reduction against the last comparable (same subs/param-count/smoke
# class) PARAMS_SMOKE.json; failing runs never reseed the baseline.
echo
echo "=== bench.py --params-ab --smoke"
if ! python bench.py --params-ab --smoke --perf-gate; then
    fail=1
    failed_files+=("bench.py --params-ab --smoke")
fi

# Flight-recorder smoke: the recorder on/off overhead A/B
# (obs/blackbox.py) plus the dump round-trip and no-stray-dump
# checks. The full lane gates the on/off grad-steps/s ratio at the
# 0.95 PERF.md floor; the smoke lane anti-ratchets against the last
# comparable (same frames/smoke class) BLACKBOX_SMOKE.json — failing
# runs never reseed the baseline.
echo
echo "=== bench.py --blackbox-ab --smoke"
if ! python bench.py --blackbox-ab --smoke --perf-gate; then
    fail=1
    failed_files+=("bench.py --blackbox-ab --smoke")
fi

# Chaos-remediation smoke: the three-arm availability drill (clean /
# chaos / chaos+remediation) from bench.py --chaos-ab. The remediated
# arm must beat the last comparable (same window/clients)
# CHAOS_SMOKE.json under --perf-gate — the anti-ratchet proves the
# remediation plane keeps EARNING its availability win, not just that
# it once did; failing runs never reseed the baseline. (The 0.822
# PERF.md floor applies only to the full lane — the smoke window is
# too short for an absolute bound.) The drill also hard-gates its own
# forensics: the postmortem bundle must exist and its root-cause walk
# must attribute the injected kill/wedge by component name.
echo
echo "=== bench.py --chaos-ab --smoke"
if ! python bench.py --chaos-ab --smoke --perf-gate; then
    fail=1
    failed_files+=("bench.py --chaos-ab --smoke")
fi

echo
if [ "${fail}" -ne 0 ]; then
    echo "FAILED files: ${failed_files[*]}"
else
    echo "all files passed"
fi
exit "${fail}"
