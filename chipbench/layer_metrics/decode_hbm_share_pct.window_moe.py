"""How near a decode step is to the bytes it must move: the least bytes
of the window's decode steps (every weight outside the routed experts
once; the routed experts that took a row, from the program's own counter;
the full layers' keys and values at the live lengths and the sliding
layers' at ``min(length, sliding_window)``, from the harness's record of
the lengths: ``flops_window_moe.decode_step_bytes``) over the peak HBM
bytes/s, as a share of the median host time of a ``step()`` without a
prefill chunk.  A run that recorded no windowed keys or no counter
(another configuration's) reads nothing."""
from chipbench import flops_window_moe
from chipbench.harness import percentile


def read(record, cell, peaks):
    f, cfg = record["facts"], cell["config"]
    calls, steps = f["decode_calls"], f["decode_step_s"]
    seen, experts = f.get("decode_window_keys"), f.get("experts")
    if not calls or not steps or not seen or not experts \
            or not experts["steps"]:
        return None
    itemsize = 2 if cfg["engine"]["dtype"] == "bfloat16" else 4
    least = flops_window_moe.decode_step_bytes(
        cfg, sum(k for _, k in calls) / len(calls), sum(seen) / len(seen),
        experts["hit"] / experts["steps"], itemsize)
    return 100.0 * least / peaks["hbm_bytes_per_s"] / percentile(steps, 50)
