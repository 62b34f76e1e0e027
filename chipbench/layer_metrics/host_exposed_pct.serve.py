"""Share of the window in which the host held the chip back: the time
from the end of each blocking fetch (``exec.fetch``, after which the
device's queue is empty) to the start of the next span that hands the
device work (``jit.dispatch``, ``kv.write``, ``kv.gather``), summed over
the window.  The log says in which spans that time lay."""
from chipbench import program_spans
from chipbench.harness import log


def read(record, cell, peaks):
    got = program_spans.load(record, cell)
    if got is None:
        return None
    spans, t0, t1 = got
    gaps = program_spans.exposed(spans, t0, t1)
    total = sum(b - a for a, b in gaps)
    parts = sorted(program_spans.split(spans, gaps).items(),
                   key=lambda kv: -kv[1])
    log(f"host exposed {total:.3f} s of {t1 - t0:.2f} s in {len(gaps)} "
        f"gaps, by span: "
        + ", ".join(f"{k} {v:.3f}" for k, v in parts if v > 5e-4))
    return 100.0 * total / (t1 - t0)
