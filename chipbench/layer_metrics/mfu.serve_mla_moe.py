"""The latent-attention + routed-experts serving loop's share of the
chip's peak: forward FLOPs of every prompt and output token the engine
processed inside the window (projections, the dense and shared
feed-forwards, the routed experts for the choices that land on a held
expert, attention over the keys read, the head per sampled token:
``flops_mla_moe.py``), over the window and the peak bf16 FLOP/s.  The
share of the whole step; a configuration of another shape reads nothing."""
from chipbench import flops_mla_moe


def read(record, cell, peaks):
    f, cfg = record["facts"], cell["config"]
    if "kv_lora_rank" not in cfg or "share" not in cfg:
        return None
    done = flops_mla_moe.serve_flops(cfg, f["layer_tokens"],
                                     f["sampled_tokens"], f["context_sum"])
    chips = cell["workload"]["chips"]
    return 100.0 * done / f["window_s"] / (chips * peaks["bf16_flops_per_s"])
