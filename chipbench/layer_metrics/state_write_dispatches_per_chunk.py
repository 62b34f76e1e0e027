"""Device programs issued to put a prefill chunk's recurrent state into
the state cache, per chunk (``req.prefill`` spans), over the window: the
``jit.dispatch`` spans recorded inside a ``state.write`` span (its
children), counted, not the span's own say-so.  A program without
recurrent state records no such span, and the metric is left out."""
from chipbench import program_spans


def read(record, cell, peaks):
    got = program_spans.load(record, cell)
    if got is None:
        return None
    spans, t0, t1 = got
    chunks = len(program_spans.named(spans, "req.prefill", t0, t1))
    writes = {s[0] for s in program_spans.named(spans, "state.write",
                                                t0, t1)}
    if not chunks or not writes:
        return None
    return sum(s[2] == "jit.dispatch" and s[1] in writes
               for s in spans) / chunks
