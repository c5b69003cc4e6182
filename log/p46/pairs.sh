#!/bin/bash
# runs of the benchmark's cells from the parent (log/parent, a git archive of the parent commit) and from
# the change (this tree, or $CHANGE), in the order given:  bash log/p46/pairs.sh <tag> cell:side:seed:trace ...
tag=$1; shift
CHANGE=${CHANGE:-.}
out=chiprun_out/p46/$tag; mkdir -p $out; root=$(pwd)
t0=$(date +%s)
for spec in "$@"; do
  IFS=: read cell side seed trace <<< "$spec"
  if [ $(( $(date +%s) - t0 )) -gt ${STOP_AFTER:-3000} ]; then echo "no time left for $spec"; continue; fi
  dir=$CHANGE; [ $side = parent ] && dir=log/parent
  f=$out/${cell}__${side}__${seed}__t$trace
  # a traced run of a latent family also writes what its readers were handed (the launches' entries)
  (cd $dir && DSA_SPAN_RECORD=$root/$f.span.json python3 benchmark/run.py --workload $cell --seed $seed --seconds 51 --trace $trace > $root/$f.out 2> $root/$f.err)
  echo "== $cell $side seed $seed trace $trace rc $? at $(( $(date +%s) - t0 )) s"
  grep -aE '^(samples|backlog|ladder|check|window|moe)' $f.out | cut -c1-400
  if [ $trace = 1 ]; then grep -aE '^(launches|engine_step_span_ms|mla_|dsa_|moe_ffn_roofline|idle_by_phase: all)' $f.out | cut -c1-600; tail -n 1 $f.out | cut -c1-7000; else tail -n 1 $f.out | cut -c1-1200; fi
done
