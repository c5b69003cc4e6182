"""Operations one training step needs per token, forward and backward,
with no recomputation counted (the MFU convention of bench.py:1829, with
causal attention added).

- every matmul parameter (all but the input embedding, which is a
  gather) costs 2 operations forward and 4 backward: 6 N;
- causal attention costs, per layer and token at sequence length s,
  2 * 2 * (s / 2) * heads * head_dim forward (QK^T and PV over the s/2
  keys a token sees on average) and twice that backward: 6 * s * h * hd.
Bound: FLOPs (a step is matmul-bound at these sizes).
"""


from benchmark.lib.weights import head_dim


def matmul_params(cfg):
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    hd = head_dim(cfg)
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    per_layer = d * h * hd + 2 * d * kvh * hd + h * hd * d + 3 * d * ff
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def flops_per_token(cfg, seq):
    attn = (6 * seq * cfg["num_attention_heads"] * head_dim(cfg)
            * cfg["num_hidden_layers"])
    return 6 * matmul_params(cfg) + attn
