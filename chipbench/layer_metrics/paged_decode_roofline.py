"""Decode attention against its roofline: per decode step of the traced
stretch the least time for the keys the live sequences' lengths need
(from the harness's own record of batch and lengths), times the layers,
over the device time of the decode-attention kernel's events there."""
from chipbench import roofline, spec


def read(record, cell, peaks):
    kernel = spec.load_module(record["bench"], "kernels", "paged_decode")
    rows = record["trace"]["kernels"].get("paged_decode", {})
    cfg = cell["config"]
    least = sum(roofline.least_seconds(
        kernel, kernel.shape(cfg, batch, keys), "decode", peaks)[0]
        for batch, keys in record["facts"]["traced"]["decode_calls"])
    return roofline.share_pct(least * cfg["num_hidden_layers"],
                              sum(r["seconds"] for r in rows.values()))
