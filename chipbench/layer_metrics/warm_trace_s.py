"""Seconds of set-up spent tracing and lowering programs in Python —
time no compile cache holds: the union of the ``jit.trace`` and
``jit.lower`` intervals that ended before the window (an inner jit's
trace lies inside its caller's, so the union and not the sum)."""
from chipbench import program_spans


def read(record, cell, peaks):
    got = program_spans.load(record, cell)
    if got is None:
        return None
    spans, t0, _ = got
    return program_spans.union_s(
        (s[3], s[4]) for s in spans
        if s[2] in ("jit.trace", "jit.lower") and s[4] <= t0)
