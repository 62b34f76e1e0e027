"""Median host time of a ``step()`` that carried no prefill chunk."""
from chipbench.harness import percentile


def read(record, cell, peaks):
    xs = record["facts"]["decode_step_s"]
    return 1e3 * percentile(xs, 50) if xs else None
