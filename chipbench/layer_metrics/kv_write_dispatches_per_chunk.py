"""Eager device operations issued to write a prefill chunk's K and V into
the page pool (``kv.write``'s ``dispatches``), per chunk (``req.prefill``
spans), over the window."""
from chipbench import program_spans


def read(record, cell, peaks):
    got = program_spans.load(record, cell)
    if got is None:
        return None
    spans, t0, t1 = got
    chunks = len(program_spans.named(spans, "req.prefill", t0, t1))
    writes = program_spans.named(spans, "kv.write", t0, t1)
    if not chunks:
        return None
    return sum(s[5]["dispatches"] for s in writes) / chunks
