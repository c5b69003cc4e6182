#!/bin/bash
# the kernel's tile sizes -> the best patched into this copy -> one traced run of code_ctx
out=chiprun_out/p45; mkdir -p $out
t0=$(date +%s)
timeout 1500 python3 log/p45/bench_prefill.py "$@" > $out/bench_prefill.txt 2> $out/bench_prefill.err; echo "bench rc $? at $(( $(date +%s) - t0 )) s"
cat $out/bench_prefill.txt | cut -c1-700; tail -n 5 $out/bench_prefill.err | cut -c1-400
python3 - <<'PY' > chiprun_out/p45/best.txt
import json
d = json.load(open("chiprun_out/p45/bench_prefill.json"))
best, cost = (2048, 512), float("inf")
for k, v in d.items():
    if not k.endswith("_H128") or "error" in v or not k.startswith("kernel_") or v["max_abs_diff"] > 0.01 * v["width"]:
        continue
    c = v["ms@32768"] + 4 * v["ms@8192"] + 4 * v["ms_5k_prompt"]
    if c < cost:
        best, cost = tuple(map(int, k.split("_")[1:3])), c
print(*best)
PY
read R K < $out/best.txt; R=${R:-2048}; K=${K:-512}; echo "best: rows $R keys $K"
sed -i "s/^_PREFILL_ROWS = [0-9]*/_PREFILL_ROWS = $R/; s/^_PREFILL_KEYS = [0-9]*/_PREFILL_KEYS = $K/" paddle_tpu/kernels/latent_attention.py
grep -n "^_PREFILL_" paddle_tpu/kernels/latent_attention.py
cell=deepseek-v3-serve.code_ctx
python3 benchmark/run.py --workload $cell --seed 4500000101 --seconds 51 --trace 1 > $out/first_traced.out 2> $out/first_traced.err; echo "traced rc $? at $(( $(date +%s) - t0 )) s"
grep -aE '^(samples|backlog|window|ladder|setup|engine|reference|gaps|check|moe|mla_|breakdown)' $out/first_traced.out | cut -c1-900
tail -n 1 $out/first_traced.out | cut -c1-7000
tail -n 8 $out/first_traced.err | cut -c1-400
# if the first traced run is correct: the parent traced on its seed, two pairs of code_ctx, one of long_ctx
if tail -n 1 $out/first_traced.out | grep -q '"correct": true'; then
  TAG=pairs1 STOP_AFTER=1500 CODE_CTX="parent:4500000101:1 parent:4500000201:0 change:4500000201:0 change:4500000202:0 parent:4500000202:0" \
    LONG_CTX="parent:4500000301:0 change:4500000301:0" bash log/p45/call2.sh
else echo "first traced run not correct: no pairs"; fi
