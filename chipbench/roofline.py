"""A kernel's share of its roofline.

The least time the chip could take for a call is the larger of its
operations over the peak FLOP/s and its bytes over the peak bytes/s; the
share is that, summed over the calls the trace holds, over the device
time of those calls.  Nothing here clips: a share over 100 % means the
count is too high or the time leaves out part of the work, and has to
show.
"""


def least_seconds(kernel, shape, phase, peaks):
    by_flops = kernel.flops(shape, phase) / peaks["bf16_flops_per_s"]
    by_bytes = kernel.bytes(shape, phase) / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("flops" if by_flops >= by_bytes
                                     else "bytes")


def share_pct(least_s, device_s):
    if not device_s:
        return None         # nothing to read: the metric is left out
    return 100.0 * least_s / device_s
