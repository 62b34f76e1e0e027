"""What a decode step hands the device, counted: over the window's steps
that hold no prefill chunk, (``jit.dispatch`` spans + host-to-device
transfers, ``h2d`` of ``exec.prep`` + eager device ops, ``eager`` of
``exec.prep`` and ``exec.fetch``) a step.  One transfer and one program
would read 2."""
import bisect

from chipbench import program_spans
from chipbench.harness import log


def decode_only_steps(spans, t0, t1):
    """For each ``serve.step`` that ended in ``(t0, t1]`` and holds no
    ``req.prefill``: the spans that began inside it."""
    steps = program_spans.named(spans, program_spans.STEP["serve"], t0, t1)
    starts = [s[3] for s in steps]
    inside = [[] for _ in steps]
    for s in spans:
        i = bisect.bisect_right(starts, s[3]) - 1
        if i >= 0 and s[3] < steps[i][4] and s is not steps[i]:
            inside[i].append(s)
    return [kids for kids in inside
            if not any(s[2] == "req.prefill" for s in kids)]


def read(record, cell, peaks):
    got = program_spans.load(record, cell)
    if got is None:
        return None
    steps = decode_only_steps(*got)
    kids = [s for step in steps for s in step]
    preps = [s for s in kids if s[2] == "exec.prep"]
    if not preps or any("h2d" not in s[5] for s in preps):
        return None
    counts = {
        "dispatches": sum(s[2] == "jit.dispatch" for s in kids),
        "h2d": sum(s[5]["h2d"] for s in preps),
        "eager": sum(s[5].get("eager", 0) for s in kids
                     if s[2] in ("exec.prep", "exec.fetch"))}
    log(f"device calls: {counts} in {len(steps)} decode-only steps")
    return sum(counts.values()) / len(steps)
