#!/bin/bash
# Runs of one cell as the driver makes them, each a new process with another
# seed: one run that compiles (kept apart), one traced run, then two sets of
# N runs. Result lines go to chiprun_out/spread/<cell>.{first,traced,set1,set2}.jsonl
#   bash benchmark/sweeps/measure_cell.sh <cell> <seconds> <runs-per-set> [first-seed]
# SKIP_TRACED=1 leaves the traced run out.
cell=$1; seconds=$2; n=$3; seed=${4:-100}
out=chiprun_out/spread; mkdir -p $out chiprun_out/logs
one() { # file trace
  seed=$((seed + 1))
  t0=$(date +%s)
  python3 benchmark/run.py --workload $cell --seed $seed --seconds $seconds --trace $2 \
    2> chiprun_out/logs/$cell.seed$seed.err | tail -n 1 >> $out/$cell.$1.jsonl
  rc=${PIPESTATUS[0]}
  echo "$cell $1 seed=$seed rc=$rc wall=$(( $(date +%s) - t0 ))s $(tail -n 1 $out/$cell.$1.jsonl | cut -c1-420)"
  return $rc
}
one first 0 || { tail -n 25 chiprun_out/logs/$cell.seed$seed.err; exit 1; }
grep -q '"correct": true' $out/$cell.first.jsonl || { echo "first run not correct"; tail -n 1 $out/$cell.first.jsonl | cut -c1-3000; exit 1; }
if [ -z "$SKIP_TRACED" ]; then one traced 1; tail -n 1 $out/$cell.traced.jsonl | cut -c1-3500; fi
for i in $(seq $n); do one set1 0; done
for i in $(seq $n); do one set2 0; done
