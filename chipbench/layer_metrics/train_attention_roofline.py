"""The train step's attention kernel against its roofline: the least
time the chip could take for the forward and backward calls the trace
holds, at the cell's shape, over the device time of those calls."""
from chipbench import roofline, spec


def read(record, cell, peaks):
    kernel = spec.load_module(record["bench"], "kernels", "train_attention")
    rows = record["trace"]["kernels"].get("train_attention", {})
    f = record["facts"]
    shape = kernel.shape(cell["config"], f["batch"], f["seq"])
    least = sum(row["calls"] * roofline.least_seconds(
        kernel, shape, phase, peaks)[0] for phase, row in rows.items())
    return roofline.share_pct(least, sum(r["seconds"] for r in rows.values()))
