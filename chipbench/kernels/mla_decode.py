"""Absorbed-form decode attention over the latent page pool
(``ops/pallas_kernels/mla_decode.py``): one query token per live sequence,
all its heads against that sequence's own cached rows.

PATTERNS matches the Pallas kernel's event, named after the jitted
wrapper around its ``pallas_call``.

The count is per layer and per decode call, from the lengths of the LIVE
sequences (rows each query reads, the new token included) — what the
absorbed form needs, not the pages' tails, a ``max_len`` window or the
128-lane padding of the row: each row of rank + rope values read once,
the queries and outputs; for every head a score of rank + rope and a sum
of rank per row.
"""

PATTERNS = {
    "decode": [r"^_mla_decode_call\S* \[tpu_custom_call\]"],
}


def shape(cfg, batch, keys):
    return {"B": batch, "keys": keys, "heads": cfg["num_attention_heads"],
            "rank": cfg["kv_lora_rank"], "rope": cfg["qk_rope_head_dim"],
            "itemsize": 2}


def flops(sh, phase="decode"):
    return 2 * sh["heads"] * (2 * sh["rank"] + sh["rope"]) * sh["keys"]


def bytes(sh, phase="decode"):
    rows = sh["keys"] * (sh["rank"] + sh["rope"]) * sh["itemsize"]
    qo = sh["B"] * sh["heads"] * (2 * sh["rank"] + sh["rope"]) \
        * sh["itemsize"]
    return rows + qo
