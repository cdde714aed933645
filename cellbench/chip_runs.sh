#!/bin/sh
# Several runs of one cell in one process tree, one after another, for
# a builder's chip call: `sh cellbench/chip_runs.sh <workload> <seconds> <trace> <seed>...`
# Each run's stdout (summary + result line) goes to chiprun_out/cellbench/runs.jsonl too.
w=$1; s=$2; t=$3; shift 3
mkdir -p chiprun_out/cellbench
for seed in "$@"; do
  python3 cellbench/run.py --workload "$w" --seed "$seed" --seconds "$s" --trace "$t" \
    2>>chiprun_out/cellbench/stderr.log | tee -a chiprun_out/cellbench/runs.jsonl | tail -n 2 | cut -c1-1800
  echo "rc=$? seed=$seed trace=$t"
done
tail -n 40 chiprun_out/cellbench/stderr.log
