#!/bin/bash
# Sets of runs of cells, from a copy of the committed files (README.md,
# "Measuring a cell for its bounds"):  sets.sh <runs> <sets> <cell>...
# Sets are numbered from FIRST_SET (1). After the sets, one run of each cell
# that measures and then traces (--trace 2), unless TRACED=0. A run that fails, or is not correct, ends
# everything: a set with such a run is no set, and chip time is dear.
# RUN_ARGS is passed on to run.py (RUN_ARGS=--rehearse tries this script on the CPU).
# Run i of every set has the seed SEED0 + i * SEED_STEP (SEED0: 3000000000;
# SEED_STEP: 1; SEED_STEP=0 runs one seed over and over, which splits the
# schedule's part of a spread from the machine's).
runs=$1; sets=$2; shift 2
first=${FIRST_SET:-1}
seed0=${SEED0:-3000000000}
step=${SEED_STEP:-1}
cd .archive_check || exit 9
out=../chiprun_out/perfbench/sets; mkdir -p $out
one() {  # tag, then run.py's arguments
  tag=$1; shift
  python3 perfbench/run.py "$@" $RUN_ARGS > $out/$tag.out 2> $out/$tag.err
  rc=$?
  echo "$tag rc=$rc $(tail -n 1 $out/$tag.out | cut -c1-360)"
  grep '"phase": "window"' $out/$tag.out | sed 's/.*"client": /  /' | cut -c1-400
  grep -E '"phase": "(capture_retaken|tail|metric_left_out)"' $out/$tag.out | cut -c1-400
  if [ $rc != 0 ] || ! tail -n 1 $out/$tag.out | grep -q '"correct": true, .*"failed": 0,'; then
    grep '"phase": "checks"' $out/$tag.out | cut -c1-1500; tail -c 3000 $out/$tag.err
    rm -rf chiprun_out; exit 1
  fi
}
for s in $(seq $first $((first + sets - 1))); do
  for cell in "$@"; do
    for i in $(seq 1 $runs); do
      one $cell.S$s.$i --workload $cell --seed $((seed0 + i * step)) --trace 0
    done
  done
done
if [ "${TRACED:-1}" != 0 ]; then
  for cell in "$@"; do
    one $cell.T --workload $cell --seed $((seed0 + 1)) --trace 2
    tail -n 1 $out/$cell.T.out | cut -c1-3500
  done
fi
# traces and run directories stay on the machine; what was printed goes back
rm -rf chiprun_out
