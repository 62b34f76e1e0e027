"""The serving loop's share of the chip's peak: forward FLOPs of every
prompt and output token the engine processed inside the window, over
the window and the peak bf16 FLOP/s."""
from chipbench import flops


def read(record, cell, peaks):
    f = record["facts"]
    done = flops.serve_flops(cell["config"], f["layer_tokens"],
                             f["sampled_tokens"], f["context_sum"])
    chips = cell["workload"]["chips"]
    return 100.0 * done / f["window_s"] / (chips * peaks["bf16_flops_per_s"])
