"""85th percentile, over ALL requests submitted in the window, of submit
-> first token (host clock after the ``step()`` that produced it; a
request that failed or never answered counts as the whole window).  The
window holds some 75 requests: the 85th is the highest percentile with
ten of them beyond it."""


def read(record, cell, peaks):
    return record["facts"]["ttft_p85_ms"]
