"""Median host time of a ``step()`` that carried at least one prefill
chunk (``executor.prefill_events`` grew)."""
from chipbench.harness import percentile


def read(record, cell, peaks):
    xs = record["facts"]["prefill_step_s"]
    return 1e3 * percentile(xs, 50) if xs else None
