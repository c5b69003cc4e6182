#!/bin/bash
# the microbenchmarks (a)-(c) of PR 46 in one call: the grouped product alone, then one cold
# prefill of each cell's median prompt at blocks of 256 / 512 with the parent's rungs and the odd ones
mkdir -p chiprun_out/p46
t0=$(date +%s)
python3 log/p46/bench_ragged.py > chiprun_out/p46/bench_ragged.out 2> chiprun_out/p46/bench_ragged.err
echo "== ragged rc $? at $(( $(date +%s) - t0 )) s"; cat chiprun_out/p46/bench_ragged.out
for spec in deepseek-v3-serve.code_ctx:1 mimo-v2.5-serve.long_in:0 glm-5-serve.long_ctx:0; do
  cell=${spec%%:*}
  python3 log/p46/bench_block.py $cell --pairs ${spec##*:} > chiprun_out/p46/bench_block_$cell.out 2> chiprun_out/p46/bench_block_$cell.err
  echo "== $cell rc $? at $(( $(date +%s) - t0 )) s"; cat chiprun_out/p46/bench_block_$cell.out; tail -n 5 chiprun_out/p46/bench_block_$cell.err | cut -c1-400
done
