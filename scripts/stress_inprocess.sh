#!/usr/bin/env bash
# Stress loop for the in-process restart engine's async-exception delivery.
#
# The engine's premise is that injection is safe: a healthy rank must NEVER die
# because a RankShouldRestart landed outside the wrapped fn (the round-2 delivery
# race, review round 2 weak #1). This loop is the regression gate: run the multi-rank
# restart tests N times (default 50) and fail on the first non-green run.
#
#   ./scripts/stress_inprocess.sh [N]
set -u
N="${1:-50}"
cd "$(dirname "$0")/.."
export PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}"
for i in $(seq 1 "$N"); do
    out=$(timeout 300 python -m pytest tests/inprocess/test_wrap.py -k MultiRank -q 2>&1)
    status=$?
    tail=$(echo "$out" | tail -1)
    echo "run $i/$N: $tail"
    if [ "$status" -ne 0 ]; then
        echo "$out"
        echo "STRESS FAILURE on run $i"
        exit 1
    fi
done
echo "all $N runs green"
