#!/bin/bash
# what call3 had no time left for: one pair each of chat and chat_short (their programs are the parent's text)
d=qwen2-7b-serve.chat; e=granite-4.0-h-micro-serve.chat_short
CHANGE=log/final bash log/p46/pairs.sh others $d:change:4600000601:0 $d:parent:4600000602:0 $d:change:4600000602:0 $e:change:4600000701:0 $e:parent:4600000701:0
