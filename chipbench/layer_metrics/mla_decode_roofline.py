"""The latent decode-attention kernel against its roofline: per decode
step of the traced stretch the least time for the rows the live
sequences' lengths need (the larger of its two floors, from the harness's
own record of batch and lengths), times the layers, over the device time
of the kernel's events there."""
from chipbench import roofline, spec


def read(record, cell, peaks):
    rows = record["trace"]["kernels"].get("mla_decode", {})
    cfg = cell["config"]
    if not rows or "kv_lora_rank" not in cfg:
        return None
    kernel = spec.load_module(record["bench"], "kernels", "mla_decode")
    least = sum(roofline.least_seconds(
        kernel, kernel.shape(cfg, batch, keys), "decode", peaks)[0]
        for batch, keys in record["facts"]["traced"]["decode_calls"])
    return roofline.share_pct(least * cfg["num_hidden_layers"],
                              sum(r["seconds"] for r in rows.values()))
