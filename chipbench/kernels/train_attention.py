"""Causal self-attention of the train step, forward and backward.

PATTERNS matches the device events of whichever attention kernel the
step runs, by phase: stock flash (jax.experimental.pallas.ops.tpu.
flash_attention) today, since the repo's own ``long_attention`` refuses
grouped-query shapes, and ``long_attention`` / ``short_attention`` should
a later PR switch.  The program puts no named scope around attention,
so the names are what XLA derives: ``_sdpa_plain`` (the jitted wrapper in
ops/nn_ops.py) for stock flash's forward, ``flash_mha_bwd_dkv_..`` and
``flash_mha_bwd_dq_..`` for its two backward kernels.

The count is what the algorithm needs, in units of u = B * Hq * S^2 * D
(one S x S x D matmul per query head, causal half): forward QK^T and PV
= 2 u per call; backward dV, dP, dS->dQ, dS->dK and the recomputed
scores = 5 u per backward pass, split evenly over the dkv and dq
kernels where the backward is two kernels, so that the parts never add
up to more than the whole.  K and V count at the KV heads they are
stored in, not at the repeated width the step feeds the kernel today.
"""

PATTERNS = {
    # (names as trace_reduce.compact writes them: "<instruction> [<custom
    # call target or fusion kind>] <first result shape>"; a Pallas kernel
    # is a "tpu_custom_call", named after the innermost named scope or
    # jitted function around its pallas_call)
    "fwd": [r"^_sdpa_plain\S* \[tpu_custom_call\]",
            # any other forward kernel: a name that ends in _fwd,
            # _fwd_kernel or flash_attention
            r"^\S*(_fwd|_fwd_kernel|flash_attention)[.\d]* "
            r"\[tpu_custom_call\]"],
    "bwd_dkv": [r"^\S*bwd_dkv\S* \[tpu_custom_call\]"],
    "bwd_dq": [r"^\S*bwd_dq\S* \[tpu_custom_call\]"],
    # one fused backward kernel: a name that ends in _bwd or _bwd_kernel
    "bwd": [r"^\S*_bwd(_kernel)?[.\d]* \[tpu_custom_call\]"],
}
UNITS = {"fwd": 2.0, "bwd_dkv": 2.5, "bwd_dq": 2.5, "bwd": 5.0}


def shape(cfg, batch, seq):
    return {"B": batch, "S": seq, "Hq": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "D": cfg["head_dim"],
            "itemsize": 2}


def flops(sh, phase):
    u = sh["B"] * sh["Hq"] * sh["S"] ** 2 * sh["D"]
    return UNITS[phase] * u


def bytes(sh, phase):
    q = sh["B"] * sh["S"] * sh["Hq"] * sh["D"] * sh["itemsize"]
    kv = sh["B"] * sh["S"] * sh["Hkv"] * sh["D"] * sh["itemsize"]
    stats = sh["B"] * sh["Hq"] * sh["S"] * 4        # log-sum-exp, f32
    if phase == "fwd":          # read q, k, v; write o and the stats
        return 2 * q + 2 * kv + stats
    whole = 4 * q + 4 * kv + stats   # read q k v o do stats; write dq dk dv
    return whole if phase == "bwd" else whole / 2
