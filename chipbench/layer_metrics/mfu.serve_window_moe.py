"""The window + full attention serving loop's share of the chip's peak:
forward FLOPs of every prompt and output token the engine processed
inside the window (projections and the output's gate, the dense and
shared feed-forwards, 8 routed experts a token, attention over the keys
each token may SEE by layer kind, the head per sampled token:
``flops_window_moe.py``), over the window and the peak bf16 FLOP/s.  The
share of the whole step; a configuration of another shape, or a run that
did not count the windowed keys, reads nothing."""
from chipbench import flops_window_moe


def read(record, cell, peaks):
    f, cfg = record["facts"], cell["config"]
    if "sliding_window" not in cfg or "seen_window_sum" not in f:
        return None
    done = flops_window_moe.serve_flops(
        cfg, f["layer_tokens"], f["sampled_tokens"], f["context_sum"],
        f["seen_window_sum"])
    chips = cell["workload"]["chips"]
    return 100.0 * done / f["window_s"] / (chips * peaks["bf16_flops_per_s"])
