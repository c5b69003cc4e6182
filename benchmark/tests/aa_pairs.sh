#!/bin/bash
# Two sets of runs of one cell as the driver's check makes them: two
# copies of one tree (unpacked beforehand from `git archive` into
# log/aa/A and log/aa/B, each with a compile cache of its own), run i of
# one copy then run i of the other, which copy goes first alternating.
# Each copy first makes one short run that compiles and is not kept.
#
#   bash benchmark/tests/aa_pairs.sh <cell> <seconds> <seed> [<seed> ...]
#
# Run on the machine that holds the chip, from the root of the checkout;
# standard output of every run goes to chiprun_out/aa/, read afterwards
# by benchmark/tests/spread_table.py.
set -u
cell=$1; seconds=$2; shift 2
root=$(pwd); out=$root/chiprun_out/aa; mkdir -p "$out"
run() {  # side, index, seed, seconds
  (cd "$root/log/aa/$1" && python3 benchmark/run.py --workload "$cell" \
      --seed "$3" --seconds "$4" --trace 0 \
      > "$out/${cell}__$1__$2__$3.out" 2> "$out/${cell}__$1__$2__$3.err")
  echo "$cell side $1 run $2 seed $3 rc $? $(tail -n 1 "$out/${cell}__$1__$2__$3.out" | cut -c 1-600)"
}
for side in A B; do
  run $side compile 77 5
  mv "$out/${cell}__${side}__compile__77.out" "$out/${cell}_${side}_compile.txt"
done
i=0
for seed in "$@"; do
  if [ $((i % 2)) -eq 0 ]; then run A $i "$seed" "$seconds"; run B $i "$seed" "$seconds"
  else run B $i "$seed" "$seconds"; run A $i "$seed" "$seconds"; fi
  i=$((i + 1))
done
