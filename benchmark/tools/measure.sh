#!/bin/bash
# Runs of one cell, one after another, each with another seed: what a chip
# call is given to measure a cell's spread. Contract lines go to stdout and
# to chiprun_out/<cell>.lines; each run's other output to chiprun_out/.
#   bash benchmark/tools/measure.sh <cell> <seconds> <first seed> <runs with --trace 0> [<runs with --trace 1>]
cell=$1; seconds=$2; seed=$3; plain=$4; traced=${5:-0}
mkdir -p chiprun_out
for i in $(seq 1 $((plain + traced))); do
  trace=0; [ "$i" -gt "$plain" ] && trace=1
  python3 -m benchmark.run --workload "$cell" --seed "$seed" --seconds "$seconds" --trace "$trace" \
    > "chiprun_out/${cell}_${seed}.out" 2> "chiprun_out/${cell}_${seed}.err"
  echo "run cell=$cell seed=$seed trace=$trace rc=$? $(tail -n 1 "chiprun_out/${cell}_${seed}.out" | cut -c1-2500)" | tee -a "chiprun_out/${cell}.lines"
  cp "benchmark_out/${cell}/steps.json" "chiprun_out/${cell}_${seed}.steps.json" 2>/dev/null
  cp "benchmark_out/${cell}/reference.log" "chiprun_out/${cell}_${seed}.reference.log" 2>/dev/null
  seed=$((seed + 1))
done
