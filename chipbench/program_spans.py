"""The program's own spans, for the per-layer metrics whose source is
``program_span``.

The program keeps one tracer (``paddle_tpu.obs.tracer()``), always on,
whose ring holds every span and instant of the run on
``time.perf_counter`` — the harness's clock, in the same process, so no
clock has to be aligned.  What has to be found is where the measured
window began; the rule is the generator's own:

- serving: the loop's requests are those submitted after the warm-up's
  ``len(prompt_lens) + clients``; the window opens with the first
  ``serve.step`` after the one in which the ``preroll_requests``-th of
  them finished, and lasts ``facts["window_s"]``;
- training: the window opens with the first ``train.step`` after the
  ``check_steps`` followed ones, and lasts ``facts["window_s"]``.

A window that does not hold exactly ``facts["steps"]`` steps, a ring
that dropped spans, or a program without the tracer (a parent commit)
gives ``None``, and the metric is left out of the line.
"""
import bisect

from chipbench.harness import log

STEP = {"serve": "serve.step", "train": "train.step"}
# spans during which the host hands the device work
DEVICE_WORK = ("jit.dispatch", "kv.write", "kv.gather")


def ring():
    """The tracer's ring as plain tuples ``(id, parent, name, start, end,
    args)`` in the order recorded (an instant's end is its start), or
    ``None``."""
    from paddle_tpu import obs

    tracer = getattr(obs, "tracer", None)
    if tracer is None:
        return None
    tracer = tracer()
    spans = [(s.id, s.parent, s.name, s.ts, s.ts + (s.dur or 0.0), s.args)
             for s in list(tracer.spans) if s.ph is None]
    log(f"program spans: {len(spans)} in a ring of {tracer.capacity}, "
        f"{tracer.dropped} dropped")
    if tracer.dropped:
        return None
    return spans


def _set_up(spans, record, cell):
    """Index into ``spans`` of the last record of set-up: every step
    recorded after it belongs to the window or follows it.  ``None``
    when the ring does not hold the set-up the cell describes."""
    traffic, kind = cell["traffic"], record["facts"]["kind"]
    if kind == "train":
        steps = [i for i, s in enumerate(spans) if s[2] == STEP[kind]]
        n = traffic["check_steps"]
        return ([-1] + steps)[n] if len(steps) >= n else None
    warm = len(traffic["prompt_lens"]) + traffic["clients"]
    submits = [s[5]["trace_id"] for s in spans if s[2] == "req.submit"]
    loop = set(submits[warm:])
    done = [i for i, s in enumerate(spans)
            if s[2] == "req.finish" and s[5]["trace_id"] in loop]
    if len(done) < traffic["preroll_requests"]:
        return None
    # a span is recorded as it ends, an instant as it happens: the first
    # step recorded after the finish is the step it happened in
    for i in range(done[traffic["preroll_requests"] - 1], len(spans)):
        if spans[i][2] == STEP[kind]:
            return i
    return None


def window(record, cell, spans):
    """``(t0, t1)`` of the measured window on the tracer's clock, or
    ``None``; ``spans`` is what :func:`ring` gave."""
    if spans is None:
        return None
    facts = record["facts"]
    step = STEP[facts["kind"]]
    last = _set_up(spans, record, cell)
    later = ([] if last is None else
             [s for s in spans[last + 1:] if s[2] == step])
    if not later:
        log("program spans: the window's first step was not found")
        return None
    t0 = later[0][3]
    t1 = t0 + facts["window_s"]
    held = sum(t0 < s[4] <= t1 for s in later)
    if held != facts["steps"]:
        log(f"program spans: the window holds {held} {step} spans, the "
            f"harness counted {facts['steps']}")
        return None
    return t0, t1


def load(record, cell):
    """``(spans, t0, t1)`` for a reader, or ``None``."""
    spans = ring()
    w = window(record, cell, spans)
    return None if w is None else (spans, *w)


def named(spans, name, t0, t1):
    """The spans of that name that ended in ``(t0, t1]``."""
    return [s for s in spans if s[2] == name and t0 < s[4] <= t1]


def union_s(intervals):
    """Seconds covered by the union of ``(start, end)`` intervals."""
    total, hi = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > hi:
            total += b - max(a, hi)
            hi = b
    return total


def exposed(spans, t0, t1):
    """The stretches of host time the chip cannot hide: from the end of
    each blocking fetch (``exec.fetch``: the device queue is empty) to
    the start of the next span that hands the device work, cut at the
    window's end.  Returns ``(start, end)`` pairs."""
    marks = sorted((s for s in spans
                    if s[2] == "exec.fetch" or s[2] in DEVICE_WORK),
                   key=lambda s: s[3])
    gaps, fetched = [], None
    for s in marks:
        if s[2] == "exec.fetch":
            fetched = s[4]
        elif fetched is not None:
            if t0 < fetched <= t1:
                gaps.append((fetched, min(s[3], t1)))
            fetched = None
    return gaps


def split(spans, gaps):
    """Seconds of the gaps by the span the host was in (self time: a
    span's part of a gap less its children's), ``outside`` being no
    span at all — between two ``step()`` calls, the harness's time."""
    gaps = sorted(gaps)
    ends = [b for _, b in gaps]
    out = {"outside": sum(b - a for a, b in gaps)}
    names = {s[0]: s[2] for s in spans}
    for _, parent, name, start, end, _ in spans:
        i = bisect.bisect_right(ends, start)
        while i < len(gaps) and gaps[i][0] < end:
            ov = min(end, gaps[i][1]) - max(start, gaps[i][0])
            out[name] = out.get(name, 0.0) + ov
            above = names.get(parent, "outside")
            out[above] = out.get(above, 0.0) - ov
            i += 1
    return out
