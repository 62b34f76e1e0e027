"""Operations and bytes a Mamba-2 / attention hybrid needs, from its
configuration's shapes.

Model FLOPs in the usual sense: two per multiply-add, matmuls and the
recurrence; the embedding lookup, the norms and the width-4 convolution
(35 k a token and layer) are left out.  The recurrence is counted in its
per-token form whatever form the program runs (``kernels/ssm_decode.py``).
"""
from chipbench.kernels import ssm_decode

STATE_ITEMSIZE = 4          # ssm_state_dtype float32 (the config's "assumed")


def kinds(cfg):
    t = cfg["layer_types"]
    return t.count("mamba"), t.count("attention")


def mamba_dims(cfg):
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    conv = inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return inner, conv


def mlp_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def mamba_matmul_params(cfg):
    inner, conv = mamba_dims(cfg)
    h = cfg["hidden_size"]
    return h * (inner + conv + cfg["mamba_n_heads"]) + inner * h \
        + mlp_params(cfg)


def attention_matmul_params(cfg):
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return 2 * h * nq + 2 * h * nkv + mlp_params(cfg)


def mamba_layer_params(cfg):
    """Every parameter of a Mamba layer: its matrices, the convolution's
    weight and bias, the gated norm, A_log, D and dt_bias, two norms."""
    inner, conv = mamba_dims(cfg)
    return (mamba_matmul_params(cfg) + conv * cfg["mamba_d_conv"] + conv
            + inner + 3 * cfg["mamba_n_heads"] + 2 * cfg["hidden_size"])


def attention_layer_params(cfg):
    return attention_matmul_params(cfg) + 2 * cfg["hidden_size"]


def params(cfg):
    """All parameters: the layers, the tied embedding, the final norm."""
    m, a = kinds(cfg)
    return (m * mamba_layer_params(cfg) + a * attention_layer_params(cfg)
            + cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"])


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def layer_matmul_params(cfg):
    m, a = kinds(cfg)
    return m * mamba_matmul_params(cfg) + a * attention_matmul_params(cfg)


def attention_flops_per_token(cfg, context):
    """QK^T and PV of one token that reads ``context`` keys, over the
    attention layers."""
    return (kinds(cfg)[1] * 4 * cfg["num_attention_heads"] * cfg["head_dim"]
            * context)


def serve_flops(cfg, tokens, sampled, context_sum):
    """Forward FLOPs of serving: ``tokens`` went through the layers,
    ``sampled`` of them through the head, and together they read
    ``context_sum`` keys in each attention layer."""
    per_token = (2 * layer_matmul_params(cfg)
                 + kinds(cfg)[0] * ssm_decode.flops(ssm_decode.shape(cfg, 1)))
    return (per_token * tokens + 2 * head_params(cfg) * sampled
            + attention_flops_per_token(cfg, context_sum))


def state_bytes_per_sequence(cfg, itemsize=2):
    """The recurrent state one sequence holds: the float32 SSM state and
    the convolution's tail (``itemsize``: the engine dtype's)."""
    m, _ = kinds(cfg)
    _, conv = mamba_dims(cfg)
    ssm = (cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]
           * STATE_ITEMSIZE)
    return m * (ssm + conv * (cfg["mamba_d_conv"] - 1) * itemsize)


def kv_bytes_per_token(cfg, itemsize=2):
    return (kinds(cfg)[1] * 2 * cfg["num_key_value_heads"] * cfg["head_dim"]
            * itemsize)


def weight_bytes(cfg, itemsize=2):
    return params(cfg) * itemsize


def decode_step_bytes(cfg, batch, keys, itemsize=2):
    """The least a decode step of ``batch`` live sequences that read
    ``keys`` keys in all must move: every weight once, each sequence's
    recurrent state in and out, the keys and values read."""
    return (weight_bytes(cfg, itemsize)
            + 2 * batch * state_bytes_per_sequence(cfg, itemsize)
            + keys * kv_bytes_per_token(cfg, itemsize))

