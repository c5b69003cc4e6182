#!/bin/bash
# call1's microbenchmarks, then code_ctx: parent and change traced on one seed, and a second pair
bash log/p46/call1.sh
c=deepseek-v3-serve.code_ctx
bash log/p46/pairs.sh first $c:parent:4600000201:1 $c:change:4600000201:1 $c:change:4600000202:0 $c:parent:4600000202:0
