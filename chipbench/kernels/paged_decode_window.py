"""Decode attention over a paged KV cache of two layer groups
(``ops/pallas_kernels/paged_decode.py`` with its windowed start): one
query token per live sequence against the keys it may SEE — every key of
the sequence on a full layer, the last ``sliding_window`` on a sliding
one.

PATTERNS matches the fused Pallas kernel's event, named after the jitted
wrapper around its ``pallas_call``; one kernel serves both kinds of
layer, so its events are summed and the floors are summed by kind.

The count is per layer and per decode call, from the lengths of the live
sequences (``keys``: the keys its queries see in all, the new token
included): K and V of those keys once at the stored KV heads, plus the
queries and outputs; QK^T and PV over them for every query head.  A
kernel that reads keys behind the window, a page's dead tail or a
``max_len`` table reads LOW against this count, never high.
"""

PATTERNS = {
    "decode": [r"^_call\S* \[tpu_custom_call\]"],
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
