"""Operations a decoder needs, from its configuration's shapes.

Model FLOPs in the usual sense: two per multiply-add, matmuls only, and
work done twice (rematerialisation, flash attention's recomputed
scores) counted once.  The embedding lookup is no matmul and is left
out.
"""


def layer_matmul_params(cfg):
    h, i, d = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return h * nq + 2 * h * nkv + nq * h + 3 * h * i


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def matmul_params(cfg):
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
            + head_params(cfg))


def attention_flops_per_token(cfg, context):
    """Forward attention FLOPs of one token that reads ``context`` keys:
    QK^T and PV, each 2 * context * head_dim per query head, per layer."""
    return (cfg["num_hidden_layers"] * 4 * cfg["num_attention_heads"]
            * cfg["head_dim"] * context)


def train_flops_per_token(cfg, seq):
    """Forward + backward (= 3 x forward) of one token in a causal
    sequence of ``seq``: 6 per matmul parameter, the head included, and
    attention over the (seq + 1) / 2 keys a token reads on average."""
    return 3 * (2 * matmul_params(cfg)
                + attention_flops_per_token(cfg, (seq + 1) / 2))


def serve_flops(cfg, tokens, sampled, context_sum):
    """Forward FLOPs of serving: ``tokens`` went through the layers
    (prompt and output tokens alike), ``sampled`` of them through the
    head (one per prompt's last position, one per decode step and
    sequence), and together they read ``context_sum`` keys."""
    return (2 * cfg["num_hidden_layers"] * layer_matmul_params(cfg) * tokens
            + 2 * head_params(cfg) * sampled
            + attention_flops_per_token(cfg, context_sum))
