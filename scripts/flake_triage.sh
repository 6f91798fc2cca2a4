#!/usr/bin/env bash
# flake_triage.sh — adjudicate deterministic-vs-flaky test failures.
#
# Reruns each given test file in ISOLATION (its own pytest process, same
# flags as tier-1) N times and prints a per-file verdict:
#
#   GREEN              0/N runs failed
#   FLAKY              some runs failed, some passed (timing/ordering)
#   DETERMINISTIC-FAIL N/N runs failed (a real bug, not a flake)
#
# The adjudication that used to be done by hand: a file that fails in
# the full suite but is GREEN here is suffering cross-test interference;
# FLAKY files need wait-predicate/timeout fixes; DETERMINISTIC-FAIL
# files have a reproducible defect.
#
# Usage:
#   scripts/flake_triage.sh [-n RUNS] tests/test_foo.py [tests/test_bar.py ...]
#   scripts/flake_triage.sh [-n RUNS]        # no args: run the quick
#                                            # suite once, triage every
#                                            # failing file it reports
set -u

RUNS=5
while getopts "n:" opt; do
    case "$opt" in
        n) RUNS="$OPTARG" ;;
        *) echo "usage: $0 [-n RUNS] [test files...]" >&2; exit 2 ;;
    esac
done
shift $((OPTIND - 1))

cd "$(dirname "$0")/.."

PER_FILE_TIMEOUT="${PER_FILE_TIMEOUT:-600}"
if [ -n "${FT_PYTEST:-}" ]; then
    # caller aligns the rerun invocation with its own (run_tests.sh sets
    # this so verdicts are adjudicated under the SAME marker filter and
    # jax platform the failure was observed under)
    read -r -a PYTEST <<< "$FT_PYTEST"
else
    PYTEST=(env JAX_PLATFORMS=cpu python -m pytest -q -m "not slow"
            -p no:cacheprovider -p no:xdist -p no:randomly)
fi

FILES=("$@")
if [ ${#FILES[@]} -eq 0 ]; then
    echo "no files given: running the quick suite once to find failures..."
    log=$(mktemp)
    "${PYTEST[@]}" tests/ --continue-on-collection-errors 2>&1 | tee "$log" \
        | tail -3
    # portable (no mapfile: macOS ships bash 3.2)
    FILES=()
    while IFS= read -r f; do
        FILES+=("$f")
    done < <(grep -aoE '^(FAILED|ERROR) [^:]+' "$log" \
        | awk '{print $2}' | sort -u)
    rm -f "$log"
    if [ ${#FILES[@]} -eq 0 ]; then
        echo "suite is green: nothing to triage"
        exit 0
    fi
    echo "triaging: ${FILES[*]}"
fi

status=0
for f in "${FILES[@]}"; do
    fails=0
    for i in $(seq "$RUNS"); do
        # bounded rerun: a file that failed by HANGING must not hang the
        # triage pass too
        if ! timeout -k 10 "$PER_FILE_TIMEOUT" "${PYTEST[@]}" "$f" \
                >/dev/null 2>&1; then
            fails=$((fails + 1))
        fi
    done
    if [ "$fails" -eq 0 ]; then
        verdict=GREEN
    elif [ "$fails" -eq "$RUNS" ]; then
        verdict=DETERMINISTIC-FAIL
        status=1
    else
        verdict=FLAKY
        status=1
    fi
    echo "$f: $verdict ($fails/$RUNS isolated runs failed)"
done
exit "$status"
