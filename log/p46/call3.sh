#!/bin/bash
# the final tree (log/final, a git archive of the staged tree) beside the parent (log/parent): three more pairs of
# code_ctx; long_in and long_ctx both traced on one seed and paired on others; one pair each of chat and chat_short
a=deepseek-v3-serve.code_ctx; b=mimo-v2.5-serve.long_in; c=glm-5-serve.long_ctx; d=qwen2-7b-serve.chat; e=granite-4.0-h-micro-serve.chat_short
CHANGE=log/final STOP_AFTER=${STOP_AFTER:-3200} bash log/p46/pairs.sh final \
  $a:change:4600000301:0 $a:parent:4600000301:0 $a:parent:4600000302:0 $a:change:4600000302:0 $a:change:4600000303:0 $a:parent:4600000303:0 \
  $b:parent:4600000401:1 $b:change:4600000401:1 $b:change:4600000402:0 $b:parent:4600000402:0 $b:parent:4600000403:0 $b:change:4600000403:0 \
  $c:parent:4600000501:1 $c:change:4600000501:1 $c:change:4600000502:0 $c:parent:4600000502:0 \
  $d:parent:4600000601:0 $d:change:4600000601:0 $e:change:4600000701:0 $e:parent:4600000701:0
