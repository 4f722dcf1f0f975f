#!/bin/sh
# Runs of one cell back to back in one checkout, so the snapshot and the
# compile cache are paid once:
#
#   benchmarks/prove.sh <outdir> <cell> <seconds> <trace 0|1> <seed>...
#
# Each run's whole output goes to <outdir>/<cell>-t<trace>-<n>-<seed>.log
# (+ .err), its last line is appended to <outdir>/<cell>.lastlines.jsonl,
# and its detail file (benchmarks/out/) is copied beside them.
set -u
out=$1 cell=$2 seconds=$3 trace=$4
shift 4
mkdir -p "$out"
n=$(ls "$out" 2>/dev/null | grep -c "^$cell-t.*\.log$")
for seed in "$@"; do
    n=$((n + 1))
    tag="$cell-t$trace-$n-$seed"
    python3 benchmarks/run.py --workload "$cell" --seed "$seed" \
        --seconds "$seconds" --trace "$trace" \
        > "$out/$tag.log" 2> "$out/$tag.err"
    rc=$?
    echo "rc=$rc $tag $(tail -n 1 "$out/$tag.log" | cut -c1-600)"
    tail -n 1 "$out/$tag.log" >> "$out/$cell.lastlines.jsonl"
    cp "benchmarks/out/$cell-$seed.json" "$out/$tag.detail.json" 2>/dev/null
done
