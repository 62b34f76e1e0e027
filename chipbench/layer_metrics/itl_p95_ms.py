"""95th percentile of ALL gaps between successive tokens of a request,
pooled over all requests of the window — a stalled step is in it."""


def read(record, cell, peaks):
    return record["facts"]["itl_p95_ms"]
