"""Mean number of sequences in a decode step, over the window's steps
that decoded at all (the harness's own per-step record)."""


def read(record, cell, peaks):
    calls = record["facts"]["decode_calls"]
    if not calls:
        return None
    return sum(batch for batch, _ in calls) / len(calls)
