"""The state-update kernel against its roofline: per decode step of the
traced stretch the least time for the live sequences' states (from the
harness's own record of the batch), times the state-space layers, over
the device time of the kernel's events there."""
from chipbench import roofline, spec


def read(record, cell, peaks):
    rows = record["trace"]["kernels"].get("ssm_decode", {})
    cfg = cell["config"]
    if not rows or "layer_types" not in cfg:
        return None
    kernel = spec.load_module(record["bench"], "kernels", "ssm_decode")
    least = sum(roofline.least_seconds(
        kernel, kernel.shape(cfg, batch), "decode", peaks)[0]
        for batch, _ in record["facts"]["traced"]["decode_calls"])
    return roofline.share_pct(least * cfg["layer_types"].count("mamba"),
                              sum(r["seconds"] for r in rows.values()))
