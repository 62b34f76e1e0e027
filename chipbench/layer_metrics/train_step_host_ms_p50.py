"""Median host time of ``CompiledTrainStep.step()`` (its ``train.step``
span: placing the batch and dispatching the program; the loss fetch is
the caller's), over the window's steps."""
from chipbench import program_spans
from chipbench.harness import percentile


def read(record, cell, peaks):
    got = program_spans.load(record, cell)
    if got is None:
        return None
    spans, t0, t1 = got
    steps = program_spans.named(spans, "train.step", t0, t1)
    if not steps:
        return None
    return 1e3 * percentile([s[4] - s[3] for s in steps], 50)
