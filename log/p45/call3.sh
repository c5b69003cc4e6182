#!/bin/bash
# the final tree (log/final, a git archive of the staged tree) beside the parent (log/parent):
# both traced on one seed in code_ctx and in long_ctx, and three more pairs of code_ctx
TAG=final CHANGE=log/final STOP_AFTER=${STOP_AFTER:-2400} \
CODE_CTX="parent:4500000401:1 change:4500000401:1 change:4500000501:0 parent:4500000501:0 parent:4500000502:0 change:4500000502:0 change:4500000503:0 parent:4500000503:0" \
LONG_CTX="parent:4500000601:1 change:4500000601:1 change:4500000602:0 parent:4500000602:0" bash log/p45/call2.sh
