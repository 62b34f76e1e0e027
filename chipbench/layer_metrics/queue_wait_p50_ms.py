"""Median, over the requests submitted in the window, of ``req.submit``
-> ``req.admit`` (the first admission); a request never admitted waits
for the rest of the window."""
from chipbench import program_spans
from chipbench.harness import percentile


def read(record, cell, peaks):
    got = program_spans.load(record, cell)
    if got is None:
        return None
    spans, t0, t1 = got
    sent = {s[5]["trace_id"]: s[3]
            for s in program_spans.named(spans, "req.submit", t0, t1)}
    admitted = {}
    for s in spans:
        if s[2] == "req.admit" and s[3] > t0:
            admitted.setdefault(s[5]["trace_id"], s[3])
    waits = [min(admitted.get(rid, t1), t1) - t for rid, t in sent.items()]
    return 1e3 * percentile(waits, 50) if waits else None
