"""The program's own account of when the device had nothing to do.

In a synchronous serving loop every idle gap of the device lies between
two moments the program knows on its own clock: the moment a blocking
read found its array ready (``exec.fetch``'s ``ready``: one in-order
stream, so everything handed to the device before has finished) and the
start of the next span that hands the device work
(``program_spans.DEVICE_WORK``).  Between the two the device is idle
whatever a profiler says, and the tracer's parents say what the host
was in.  The account needs no profiler session, so it covers the whole
window; over the traced stretch it can be held against the device
trace's idle seconds (``idle_seen_pct.serve``).  What it cannot see is
the latency of a launch and of the host's wake-up, which no host span
bounds; behind a final prefill chunk it begins early by the page
writer's device time (the writer is dispatched after the chunk program
and before the read).

A ring whose ``exec.fetch`` spans carry no ``ready`` (a parent commit)
gives ``None`` everywhere, and the metric is left out of the line.
"""
from chipbench import program_spans
from chipbench.harness import log

FETCH, DISPATCH = "exec.fetch", "jit.dispatch"
# whose self time inside a gap is the scheduler's own Python
SCHED = ("serve.step", "serve.sweep", "serve.decode", "serve.admit",
         "req.prefill")


def _marks(spans):
    """The blocking reads and the spans that hand the device work, in
    the order they began; ``None`` where a read does not say when it was
    ready."""
    marks = sorted((s for s in spans
                    if s[2] == FETCH or s[2] in program_spans.DEVICE_WORK),
                   key=lambda s: s[3])
    reads = [s for s in marks if s[2] == FETCH]
    if not reads or any("ready" not in s[5] for s in reads):
        return None
    return marks


def starved(spans, t0, t1):
    """The stretches in which the device had nothing to do: from each
    read's ``ready`` to the start of the next span that hands the device
    work, cut at both ends of ``(t0, t1)``.  ``(start, end)`` pairs."""
    marks = _marks(spans)
    if marks is None:
        return None
    gaps, ready = [], None
    for s in marks:
        if s[2] == FETCH:
            ready = s[5]["ready"]
        elif ready is not None:
            a, b = max(ready, t0), min(s[3], t1)
            if b > a:
                gaps.append((a, b))
            ready = None
    return gaps


def drained_dispatches(spans, t0, t1):
    """``(dispatch, read)`` pairs: each ``jit.dispatch`` that was the
    first thing handed to the device since a read found it drained,
    with the next read, for the reads that ended in ``(t0, t1]``.  From
    the dispatch's start to that read's ``ready`` the device had work
    in flight."""
    marks = _marks(spans)
    if marks is None:
        return None
    pairs, drained, first = [], False, None
    for s in marks:
        if s[2] == FETCH:
            if first is not None and t0 < s[4] <= t1:
                pairs.append((first, s))
            drained, first = True, None
        else:
            if drained and s[2] == DISPATCH:
                first = s
            drained = False
    return pairs


def traced_stretch(record, spans, t1):
    """``(start, end)`` of the traced stretch on the tracer's clock:
    first start to last end of the ``facts["traced"]["steps"]``
    ``serve.step`` spans that follow the window.  ``None`` unless
    exactly that many end within the device trace's ``window_s`` of the
    first's start (the loop's tail comes after the profiler stopped)."""
    traced, trace = record["facts"].get("traced"), record.get("trace")
    if not traced or not trace:
        return None
    later = [s for s in spans
             if s[2] == program_spans.STEP["serve"] and s[3] >= t1]
    n = traced["steps"]
    if not n or len(later) < n:
        log(f"device account: {len(later)} steps follow the window, the "
            f"harness traced {n}")
        return None
    start = later[0][3]
    held = sum(s[4] <= start + trace["window_s"] for s in later)
    if held != n:
        log(f"device account: {held} steps end inside the traced "
            f"stretch, the harness counted {n}")
        return None
    return start, later[n - 1][4]


def account(spans, gaps):
    """Seconds of ``gaps`` by who held the device back: ``copy`` (inside
    ``exec.fetch``, from ``ready`` to its end: the bytes' way to the
    host and the eager ops' tail), ``sched`` (self time of the
    scheduler's spans), ``harness`` (no span at all: between two
    ``step()`` calls) and ``prep`` (every other span: ``exec.prep``'s
    reservations, tables and transfers, and what else the data plane
    does before its program); they add up to ``total``."""
    by_span = program_spans.split(spans, gaps)
    total = sum(b - a for a, b in gaps)
    out = {"total": total, "copy": by_span.get(FETCH, 0.0),
           "sched": sum(by_span.get(name, 0.0) for name in SCHED),
           "harness": by_span["outside"]}
    out["prep"] = total - out["copy"] - out["sched"] - out["harness"]
    log("device account: starved "
        + ", ".join(f"{k} {v:.3f}" for k, v in out.items())
        + f" s in {len(gaps)} gaps; by span: "
        + ", ".join(f"{k} {v:.3f}" for k, v in sorted(
            by_span.items(), key=lambda kv: -kv[1]) if v > 5e-4))
    return out


def part_ms_per_step(record, cell, part):
    """One part of the window's account, in ms a step: what the three
    ``starved_*_ms_per_step`` readers return."""
    got = program_spans.load(record, cell)
    if got is None:
        return None
    spans, t0, t1 = got
    gaps = starved(spans, t0, t1)
    if gaps is None:
        return None
    return 1e3 * account(spans, gaps)[part] / record["facts"]["steps"]
