"""How near a decode step is to the bytes it must move: the least bytes
of the window's decode steps (every weight outside the routed experts
once; the routed experts that took a row, from the program's own counter;
the live latent rows, from the harness's record of the lengths:
``flops_mla_moe.decode_step_bytes``) over the peak HBM bytes/s, as a
share of the median host time of a ``step()`` without a prefill chunk.
A run that recorded no counter (another configuration's) reads nothing."""
from chipbench import flops_mla_moe
from chipbench.harness import percentile


def read(record, cell, peaks):
    f, cfg = record["facts"], cell["config"]
    calls, steps, experts = f["decode_calls"], f["decode_step_s"], \
        f.get("experts")
    if not calls or not steps or not experts or not experts["steps"]:
        return None
    itemsize = 2 if cfg["engine"]["dtype"] == "bfloat16" else 4
    keys = sum(k for _, k in calls) / len(calls)
    least = flops_mla_moe.decode_step_bytes(
        cfg, keys, experts["hit"] / experts["steps"], itemsize)
    return 100.0 * least / peaks["hbm_bytes_per_s"] / percentile(steps, 50)
