#!/usr/bin/env bash
# Tier-1 gate: fast import sweep, monotonic-clock static sweep, then the
# test suite.  The import sweep catches JAX API breaks in seconds instead
# of surfacing them as collection errors three minutes in.
#
#   scripts/check.sh          full gate: imports + clock sweep
#                             + serving perf baseline + tier-1 suite;
#                             FAILS if any single test exceeds
#                             REPRO_TEST_TIME_LIMIT seconds (default
#                             120 — keeps the growing suite tractable;
#                             see tests/conftest.py)
#   scripts/check.sh --fast   skip the benchmark gate; run tier-1 with
#                             no per-test time limit (the quick
#                             inner-loop check)
#
# Both modes write check_summary.json (machine-readable: tier-1
# pass/fail/skip counts, baseline-gate verdict, slowest 5 tests) so CI
# and the growth driver can gate without scraping stdout.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

FAST=0
for arg in "$@"; do
    case "$arg" in
        --fast) FAST=1 ;;
        *) echo "unknown flag: $arg (supported: --fast)" >&2; exit 2 ;;
    esac
done

echo "== import sweep ==" >&2
python - <<'PY'
import importlib
MODULES = [
    "repro.configs",
    "repro.core",
    "repro.data",
    "repro.kernels",
    "repro.launch",
    "repro.models",
    "repro.serving",
    "repro.training",
]
for mod in MODULES:
    importlib.import_module(mod)
    print(f"  ok {mod}")
PY

echo "== monotonic-clock static sweep ==" >&2
# serving/launch timing must route through serving/telemetry.py's
# default_clock (time.time() is not monotonic; scattering perf_counter
# defeats clock injection).  Only telemetry.py may touch time.* —
# fail on any new direct call in src/.
CLOCK_OFFENDERS=$(grep -rn --include='*.py' \
    -e 'time\.time()' -e 'time\.perf_counter()' -e 'time\.monotonic()' \
    src/ | grep -v 'src/repro/serving/telemetry\.py' || true)
if [ -n "$CLOCK_OFFENDERS" ]; then
    echo "direct clock calls outside telemetry.py:" >&2
    echo "$CLOCK_OFFENDERS" >&2
    exit 1
fi
echo "  ok (no direct time.time/perf_counter/monotonic in src/)" >&2

BASELINE_VERDICT="skipped"
if [ "$FAST" != "1" ]; then
    echo "== serving perf baseline (incl. open-loop + quant capacity) ==" >&2
    # the baseline gates the closed-loop QoE numbers AND the open-loop
    # scenario (Poisson arrivals into a live engine): token counts
    # exactly, plus chunked-prefill interleaving strictly beating
    # monolithic-prefill stalls on decode inter-token p99, plus the
    # int8-KV capacity scenario (capacity_* counters exact) and the
    # trace-neutrality leg (traced tokens == untraced tokens).
    if python -m benchmarks.serving_throughput --requests 12 \
        --check benchmarks/serving_baseline.json >&2; then
        BASELINE_VERDICT="pass"
    else
        BASELINE_VERDICT="fail"
        python scripts/_check_summary.py --junit "" \
            --baseline "$BASELINE_VERDICT" --out check_summary.json
        exit 1
    fi
    # any single test exceeding the limit fails the gate (slow-test
    # creep is a regression too); override with REPRO_TEST_TIME_LIMIT=0.
    # 180 leaves headroom for the slowest pre-existing test
    # (test_federated.py::test_full_private_pipeline measures 140-175s
    # on the current reference host, code unchanged — the budget gates
    # regressions, not hardware variance)
    export REPRO_TEST_TIME_LIMIT="${REPRO_TEST_TIME_LIMIT-180}"
    echo "== tier-1 tests ==" >&2
else
    echo "== tier-1 tests (fast: no benchmark gate) ==" >&2
fi

JUNIT="$(mktemp /tmp/check_junit.XXXXXX.xml)"
TESTS_OK=0
python -m pytest -x -q --durations=15 --junitxml="$JUNIT" || TESTS_OK=$?
python scripts/_check_summary.py --junit "$JUNIT" \
    --baseline "$BASELINE_VERDICT" --out check_summary.json
rm -f "$JUNIT"
exit "$TESTS_OK"
