#!/bin/sh
# The measurement the bounds were set from: for one cell, two sets of runs
# with the same seeds in both, then three traced runs on further seeds, all in
# one call.  Usage (through the chip tool, from the root of the checkout):
#   sh benchmark/sets.sh <cell> <seconds> <out-prefix> <seed> [<seed> ...]
cell=$1; seconds=$2; prefix=$3; shift 3
mkdir -p chiprun_out
for set in A B; do
  for seed in "$@"; do
    out=chiprun_out/${prefix}_${cell}_${set}_${seed}
    python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds "$seconds" --trace 0 > "$out.out" 2> "$out.err"
    echo "rc=$? set=$set seed=$seed $(tail -1 "$out.out" | cut -c1-900)"
    grep "window:\|memory:\|warm-up sweep\|output check took\|not compared" "$out.out" | grep -v "_max" | cut -c1-200
  done
done
n=0
for seed in "$@"; do
  n=$((n + 1)); [ "$n" -gt 3 ] && break
  traced=$((seed + 7))
  out=chiprun_out/${prefix}_${cell}_T_${traced}
  python3 benchmark/run.py --workload "$cell" --seed "$traced" --seconds "$seconds" --trace 1 > "$out.out" 2> "$out.err"
  echo "rc=$? traced seed=$traced $(tail -1 "$out.out" | cut -c1-2400)"
  grep "window:\|flash_\|nothing to read" "$out.out" | cut -c1-200
done
