"""How near a decode step is to the bytes it must move: the least bytes
of the window's decode steps (every weight once, the recurrent state of
the live sequences in and out, the keys and values they read; the
harness's own record of batch and lengths) over the peak HBM bytes/s,
as a share of the median host time of a ``step()`` without a prefill
chunk."""
from chipbench import flops_hybrid
from chipbench.harness import percentile


def read(record, cell, peaks):
    f = record["facts"]
    calls, steps = f["decode_calls"], f["decode_step_s"]
    if not calls or not steps or "layer_types" not in cell["config"]:
        return None
    itemsize = 2 if cell["config"]["engine"]["dtype"] == "bfloat16" else 4
    least = sum(flops_hybrid.decode_step_bytes(cell["config"], batch, keys,
                                               itemsize)
                for batch, keys in calls) / len(calls)
    return 100.0 * least / peaks["hbm_bytes_per_s"] / percentile(steps, 50)
