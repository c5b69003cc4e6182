#!/bin/bash
# parent (log/parent, a git archive of the parent commit) against the change (this tree or $CHANGE):
# code_ctx pairs, both traced on one seed, then long_ctx a pair and both traced
CHANGE=${CHANGE:-.}
out=chiprun_out/p45/${TAG:-pairs}; mkdir -p $out; root=$(pwd)
t0=$(date +%s)
run() { # side dir cell seed trace
  (cd $2 && python3 benchmark/run.py --workload $3 --seed $4 --seconds 51 --trace $5 > $root/$out/$3__$1__$4__t$5.out 2> $root/$out/$3__$1__$4__t$5.err)
  echo "== $3 $1 seed $4 trace $5 rc $? at $(( $(date +%s) - t0 )) s"
  f=$out/$3__$1__$4__t$5.out
  grep -aE '^(samples|backlog|ladder|check|window)' $f | cut -c1-400
  if [ $5 = 1 ]; then grep -aE '^(launches|engine_step_span_ms|mla_|dsa_|idle_by_phase: all)' $f | cut -c1-600; tail -n 1 $f | cut -c1-6000; else tail -n 1 $f | cut -c1-900; fi
}
cell=deepseek-v3-serve.code_ctx
for spec in $CODE_CTX; do   # side:seed:trace
  IFS=: read side seed trace <<< "$spec"
  if [ $side = parent ]; then run parent log/parent $cell $seed $trace; else run change $CHANGE $cell $seed $trace; fi
done
cell=glm-5-serve.long_ctx
for spec in $LONG_CTX; do
  IFS=: read side seed trace <<< "$spec"
  if [ $(( $(date +%s) - t0 )) -gt ${STOP_AFTER:-3000} ]; then echo "no time left for $cell $spec"; continue; fi
  if [ $side = parent ]; then run parent log/parent $cell $seed $trace; else run change $CHANGE $cell $seed $trace; fi
done
