#!/bin/bash
# Pairs of runs of two trees on one machine:  pairs.sh <pairs> <cell>...
# The parent runs from .parent_check/ and the change from .archive_check/
# (each a copy of what git would commit of its tree: README.md, "Two trees
# on one machine"), in the order P C C P P C ...; pair i of a cell has the
# seed SEED0 + i on both sides (SEED0: 3000000000). What each run printed is
# kept as sets.sh keeps it, the parent's as set 1 and the change's as set 2
# under chiprun_out/perfbench/pairs/, so that
#   python3 perfbench/spread.py chiprun_out/perfbench/pairs --judge
# reads them and says of every bounded metric what the check would.
# A run that fails, or is not correct, ends everything.
# RUN_ARGS is passed on to run.py; all runs are --trace 0 unless it says otherwise.
pairs=$1; shift
seed0=${SEED0:-3000000000}
top=$PWD
out=$top/chiprun_out/perfbench/pairs; mkdir -p $out
one() {  # side's directory, tag, then run.py's arguments
  cd $top/$1 || exit 9; tag=$2; shift 2
  python3 perfbench/run.py "$@" --trace 0 $RUN_ARGS > $out/$tag.out 2> $out/$tag.err
  rc=$?
  echo "$tag rc=$rc $(tail -n 1 $out/$tag.out | cut -c1-360)"
  rm -rf chiprun_out
  if [ $rc != 0 ] || ! tail -n 1 $out/$tag.out | grep -q '"correct": true, .*"failed": 0,'; then
    grep '"phase": "checks"' $out/$tag.out | cut -c1-1500; tail -c 3000 $out/$tag.err
    exit 1
  fi
}
for cell in "$@"; do
  for i in $(seq 1 $pairs); do
    sides=".parent_check:1 .archive_check:2"
    [ $((i % 2)) = 0 ] && sides=".archive_check:2 .parent_check:1"
    for side in $sides; do
      one ${side%:*} $cell.S${side#*:}.$i --workload $cell --seed $((seed0 + i))
    done
  done
done
