"""Operations and bytes a latent-attention decoder with routed experts
needs, from its configuration's shapes, for THIS CHIP'S SHARE: the
experts held here and the slice of the vocabulary.

Model FLOPs in the usual sense: two per multiply-add of every matmul; the
embedding lookup, the norms, the rope and the router's top-k are left
out.  Attention is counted in the expanded form (a query of nope + rope
against each key, a value of v back), which is the least the algorithm
needs; the absorbed form the decode kernel runs costs more operations for
fewer bytes and is counted in ``kernels/mla_decode.py``.  The routed
experts are counted for the choices that land on a HELD expert only —
``num_experts_per_tok * held / router_experts`` a token a layer, the
expectation under the router's own width (2 of 8 here) — never for the
rows a held expert is shown and scales by zero.
"""


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def attention_params(cfg):
    h, nh, r, dn, dr, dv = _dims(cfg)
    return h * nh * (dn + dr) + h * (r + dr) + r * nh * (dn + dv) \
        + nh * dv * h


def expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_ffn_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg):
    return cfg["hidden_size"] * cfg["share"]["router_experts"]


def layers(cfg):
    """(dense layers, expert layers)."""
    k = cfg["first_k_dense_replace"]
    return k, cfg["num_hidden_layers"] - k


def held_choices_per_token(cfg):
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["share"]["router_experts"])


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def params(cfg):
    """Every parameter this chip holds."""
    h = cfg["hidden_size"]
    d, m = layers(cfg)
    norms = 2 * h + cfg["kv_lora_rank"] \
        + cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    per_layer = attention_params(cfg) + norms
    moe = (router_params(cfg) + cfg["share"]["router_experts"]
           + (cfg["num_experts"] + cfg["num_shared_experts"])
           * expert_params(cfg))
    return ((d + m) * per_layer + d * dense_ffn_params(cfg) + m * moe
            + 2 * head_params(cfg) + h)


def matmul_params_per_token(cfg):
    """Parameters a token's forward multiplies with, over the layers."""
    d, m = layers(cfg)
    moe = (router_params(cfg)
           + (cfg["num_shared_experts"] + held_choices_per_token(cfg))
           * expert_params(cfg))
    return ((d + m) * attention_params(cfg) + d * dense_ffn_params(cfg)
            + m * moe)


def attention_flops(cfg, context):
    """Scores and values of tokens that read ``context`` keys in all,
    over the layers, expanded form."""
    _, nh, _, dn, dr, dv = _dims(cfg)
    return cfg["num_hidden_layers"] * 2 * nh * (dn + dr + dv) * context


def serve_flops(cfg, tokens, sampled, context_sum):
    """Forward FLOPs of serving: ``tokens`` went through the layers,
    ``sampled`` of them through the head, and together they read
    ``context_sum`` keys in each layer."""
    return (2 * matmul_params_per_token(cfg) * tokens
            + 2 * head_params(cfg) * sampled
            + attention_flops(cfg, context_sum))


def latent_bytes_per_token(cfg, itemsize=2):
    """The cached row of one token over the layers, as published
    (rank + rope values, no padding)."""
    return (cfg["num_hidden_layers"]
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize)


def decode_step_bytes(cfg, keys, experts_hit, itemsize=2):
    """The least a decode step must move: every weight outside the routed
    experts once (attention, dense layers, shared experts, routers, the
    head), the routed experts that took a row (``experts_hit``: (layer,
    expert) pairs, from the program's counter) and the live latent rows
    (``keys`` tokens read in all)."""
    d, m = layers(cfg)
    fixed = ((d + m) * attention_params(cfg) + d * dense_ffn_params(cfg)
             + m * (router_params(cfg)
                    + cfg["num_shared_experts"] * expert_params(cfg))
             + head_params(cfg))
    return ((fixed + experts_hit * expert_params(cfg)) * itemsize
            + keys * latent_bytes_per_token(cfg, itemsize))
