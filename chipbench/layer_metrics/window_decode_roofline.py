"""The paged decode-attention kernel against its roofline in a cache of
two layer groups: per decode step of the traced stretch the least time
for the keys the live sequences' queries SEE (the larger of the kernel's
two floors; every key on a full layer, the last ``sliding_window`` on a
sliding one, from the harness's own record of batch and lengths), times
the layers of each kind, over the device time of the kernel's events
there."""
from chipbench import flops_window_moe, roofline, spec


def read(record, cell, peaks):
    rows = record["trace"]["kernels"].get("paged_decode_window", {})
    traced, cfg = record["facts"].get("traced") or {}, cell["config"]
    seen = traced.get("decode_window_keys")
    if not rows or not seen or "sliding_window" not in cfg:
        return None
    kernel = spec.load_module(record["bench"], "kernels",
                              "paged_decode_window")
    full, sliding, _, _ = flops_window_moe.layers(cfg)

    def least(batch, keys):
        return roofline.least_seconds(
            kernel, kernel.shape(cfg, batch, keys), "decode", peaks)[0]

    total = sum(full * least(batch, keys) + sliding * least(batch, windowed)
                for (batch, keys), windowed in zip(traced["decode_calls"],
                                                   seen))
    return roofline.share_pct(total,
                              sum(r["seconds"] for r in rows.values()))
