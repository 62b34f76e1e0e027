"""The hybrid serving loop's share of the chip's peak: forward FLOPs of
every prompt and output token the engine processed inside the window
(projections, MLP, the recurrence, attention over the keys read, the head
per sampled token: ``flops_hybrid.py``), over the window and the peak
bf16 FLOP/s."""
from chipbench import flops_hybrid


def read(record, cell, peaks):
    f = record["facts"]
    done = flops_hybrid.serve_flops(cell["config"], f["layer_tokens"],
                                    f["sampled_tokens"], f["context_sum"])
    chips = cell["workload"]["chips"]
    return 100.0 * done / f["window_s"] / (chips * peaks["bf16_flops_per_s"])
