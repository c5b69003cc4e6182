#!/bin/bash
# Several runs of one cell in one call to the chip, each a process of
# its own, their whole output kept under chiprun_out/<tag>/ and the
# lines that matter shown at the end.
#   bash benchmark/tests/sweep.sh <tag> <cell> "<args of run 1>" "<args of run 2>" ...
# Arguments are chip_tool.py's (--seed, --rate, --trace, --control, --set).
tag=$1; cell=$2; shift 2
out=chiprun_out/$tag; mkdir -p "$out"
i=0
for args in "$@"; do
  i=$((i + 1))
  python3 benchmark/tests/chip_tool.py --workload "$cell" --seconds 51 $args \
    > "$out/run$i.log" 2> "$out/run$i.err"
  echo "== run $i: $cell $args (exit $?)"
  grep -aE '^(tokens|samples|backlog|window|ladder|setup|engine|reference|gaps|check|moe|moe_ffn_roofline|paged_decode_roofline):' "$out/run$i.log"
  tail -n 1 "$out/run$i.log" | cut -c1-6000
done
