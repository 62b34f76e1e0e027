"""Median time the device had a decode step in flight: over the window's
decode dispatches issued on a drained device (nothing handed to it since
a blocking read found it ready), from the dispatch's START to the decode
read's ``ready`` — the device's decode step plus launch and wake-up,
over the whole window and with no profiler.  Beside
``decode_step_ms_p50`` it splits a step into the device's part and the
host's."""
from chipbench import device_account, program_spans
from chipbench.harness import log, percentile


def read(record, cell, peaks):
    got = program_spans.load(record, cell)
    if got is None:
        return None
    spans, t0, t1 = got
    pairs = device_account.drained_dispatches(spans, t0, t1)
    if pairs is None:
        return None
    # a decode's dispatch and its read are children of one serve.decode
    xs = [f[5]["ready"] - d[3] for d, f in pairs
          if f[5].get("what") == "decode" and d[1] == f[1]]
    log(f"decode in flight: {len(xs)} dispatches on a drained device of "
        f"{len(pairs)} drained dispatches in the window")
    return 1e3 * percentile(xs, 50) if xs else None
