#!/bin/sh
# Run one cell several times in one call, one process after the other (the
# parent never touches JAX, so each run owns the chip), and keep every run's
# output:
#
#   benchmarks/prove.sh <out-dir> <cell> <seconds> <trace 0|1> "<seed> <seed> ..." [more run.py arguments]
#
# e.g.  chiprun -- sh benchmarks/prove.sh chiprun_out/a higgs_train 20 0 "11 12 13"
# Writes <out-dir>/<cell>.t<trace>.<n>.s<seed>.out/.err and prints each run's
# wall seconds and last line (the result).
out=$1; cell=$2; seconds=$3; trace=$4; seeds=$5; shift 5
mkdir -p "$out"
n=0
for seed in $seeds; do
  n=$((n + 1))
  base="$out/$cell.t$trace.$n.s$seed"
  t0=$(date +%s)
  python3 "$(dirname "$0")/run.py" --workload "$cell" --seed "$seed" \
    --seconds "$seconds" --trace "$trace" "$@" >"$base.out" 2>"$base.err"
  rc=$?
  echo "== $cell trace=$trace seed=$seed rc=$rc wall=$(( $(date +%s) - t0 ))s"
  tail -n 1 "$base.out"
done
