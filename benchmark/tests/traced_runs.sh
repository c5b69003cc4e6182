#!/bin/bash
# Runs of one cell from the root of the checkout, one seed each: traced
# (--trace 1), or with a third argument of 1 untraced through chip_tool.py
# with the int8 control's numbers read over the same sample. Standard
# output goes to chiprun_out/traced/ or chiprun_out/control/.
#
#   bash benchmark/tests/traced_runs.sh <cell> <seconds> <control 0|1> <seed> ...
set -u
cell=$1; seconds=$2; control=$3; shift 3
if [ "$control" = 1 ]; then dir=chiprun_out/control; else dir=chiprun_out/traced; fi
mkdir -p $dir
for seed in "$@"; do
  f=$dir/${cell}__${seed}
  if [ "$control" = 1 ]; then
    python3 benchmark/tests/chip_tool.py --workload "$cell" --seed "$seed" \
        --seconds "$seconds" --trace 0 --control 1 > $f.out 2> $f.err
  else
    python3 benchmark/run.py --workload "$cell" --seed "$seed" \
        --seconds "$seconds" --trace 1 > $f.out 2> $f.err
  fi
  echo "$cell seed $seed control $control rc $? $(tail -n 1 $f.out | cut -c 1-300)"
done
