"""Operations and bytes a decoder of sliding-window and full attention
layers with a gated output and routed experts needs, from its
configuration's shapes: every expert and the whole vocabulary are held.

Model FLOPs in the usual sense: two per multiply-add of every matmul; the
embedding lookup, the norms, the rope, the gate's sigmoid and the
router's top-k are left out.  Attention is counted over the keys a query
may SEE: every earlier key on a full layer, the last ``sliding_window``
on a sliding one — what the mask leaves, not what a kernel moves.  The
routed experts are counted for the ``num_experts_per_tok`` a token
chooses, never for the rows an expert is shown and scales by zero.
"""


def layer_types(cfg):
    return list(cfg["layer_types"])[:cfg["num_hidden_layers"]]


def layers(cfg):
    """(full layers, sliding layers, dense layers, expert layers)."""
    kinds = layer_types(cfg)
    full = kinds.count("full_attention")
    dense = cfg["num_dense_layers"]
    return full, len(kinds) - full, dense, len(kinds) - dense


def attention_params(cfg):
    """q, the output's gate and o [H, heads * D]; k and v
    [H, kv heads * D]."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return (3 * h * cfg["num_attention_heads"] * d
            + 2 * h * cfg["num_key_value_heads"] * d)


def expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_ffn_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg):
    return cfg["hidden_size"] * cfg["num_experts"]


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def params(cfg):
    """Every parameter the chip holds."""
    h = cfg["hidden_size"]
    _, _, d, m = layers(cfg)
    norms = 4 * h + 2 * cfg["head_dim"]
    moe = (router_params(cfg) + cfg["num_experts"]
           + (cfg["num_experts"] + cfg["num_shared_experts"])
           * expert_params(cfg))
    return ((d + m) * (attention_params(cfg) + norms)
            + d * dense_ffn_params(cfg) + m * moe
            + 2 * head_params(cfg) + h)


def matmul_params_per_token(cfg):
    """Parameters a token's forward multiplies with, over the layers."""
    _, _, d, m = layers(cfg)
    moe = (router_params(cfg)
           + (cfg["num_shared_experts"] + cfg["num_experts_per_tok"])
           * expert_params(cfg))
    return ((d + m) * attention_params(cfg) + d * dense_ffn_params(cfg)
            + m * moe)


def seen_by_window(first, n, window):
    """Keys the ``n`` tokens at positions ``first ..`` see in a sliding
    layer, in all: ``min(position + 1, window)`` each."""
    last = first + n
    ramp_end = min(max(window - 1, first), last)    # positions < window - 1
    ramp = (ramp_end * (ramp_end + 1) - first * (first + 1)) // 2 \
        if ramp_end > first else 0
    return ramp + (last - ramp_end) * window


def attention_flops(cfg, seen_full, seen_window):
    """Scores and values of tokens that see ``seen_full`` keys in all in
    each full layer and ``seen_window`` in each sliding one."""
    full, sliding, _, _ = layers(cfg)
    per_key = 4 * cfg["num_attention_heads"] * cfg["head_dim"]
    return per_key * (full * seen_full + sliding * seen_window)


def serve_flops(cfg, tokens, sampled, seen_full, seen_window):
    """Forward FLOPs of serving: ``tokens`` went through the layers,
    ``sampled`` of them through the head, and together they saw
    ``seen_full`` / ``seen_window`` keys in each layer of that kind."""
    return (2 * matmul_params_per_token(cfg) * tokens
            + 2 * head_params(cfg) * sampled
            + attention_flops(cfg, seen_full, seen_window))


def kv_bytes_per_token(cfg, itemsize=2):
    """One token's keys and values in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def decode_step_bytes(cfg, keys_full, keys_window, experts_hit, itemsize=2):
    """The least a decode step must move: every weight outside the routed
    experts once (attention, dense layers, shared experts, routers, the
    head), the routed experts that took a row (``experts_hit``: (layer,
    expert) pairs, from the program's counter), the full layers' keys and
    values at the live lengths (``keys_full`` tokens read in all) and the
    sliding layers' at ``min(length, window)`` (``keys_window``)."""
    full, sliding, d, m = layers(cfg)
    fixed = ((d + m) * attention_params(cfg) + d * dense_ffn_params(cfg)
             + m * (router_params(cfg)
                    + cfg["num_shared_experts"] * expert_params(cfg))
             + head_params(cfg))
    return ((fixed + experts_hit * expert_params(cfg)) * itemsize
            + (full * keys_full + sliding * keys_window)
            * kv_bytes_per_token(cfg, itemsize))
