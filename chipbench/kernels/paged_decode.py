"""Decode attention over the paged KV pool: one query token per live
sequence against that sequence's own keys and values.

PATTERNS matches the fused Pallas kernel (ops/pallas_kernels/
paged_decode.py) and jax's stock paged_attention kernel, whichever the
engine resolves to.

The count is per layer and per decode call, from the lengths of the live
sequences (keys each query reads, the new token included) — what the
algorithm needs, not the ``max_len`` window the kernel moves today: K
and V of those keys once at the stored KV heads, plus the queries and
outputs; QK^T and PV over them for every query head.
"""

PATTERNS = {
    # "_call" is the jitted wrapper around the fused kernel's pallas_call
    # (ops/pallas_kernels/paged_decode.py)
    "decode": [r"^_call\S* \[tpu_custom_call\]",
               r"^\S*(paged_decode|paged_attention|paged_flash)\S* "
               r"\[tpu_custom_call\]"],
}


def shape(cfg, batch, keys):
    return {"B": batch, "keys": keys, "Hq": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "D": cfg["head_dim"],
            "itemsize": 2}


def flops(sh, phase="decode"):
    return 4 * sh["Hq"] * sh["D"] * sh["keys"]


def bytes(sh, phase="decode"):
    kv = 2 * sh["keys"] * sh["Hkv"] * sh["D"] * sh["itemsize"]
    qo = 2 * sh["B"] * sh["Hq"] * sh["D"] * sh["itemsize"]
    return kv + qo
