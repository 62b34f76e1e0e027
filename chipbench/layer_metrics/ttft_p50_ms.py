"""Median, over ALL requests submitted in the window, of submit -> first
token: the statistic that the window's few dozen long requests support
(their 95th percentile is the third-largest of some sixty)."""


def read(record, cell, peaks):
    return record["facts"]["ttft_p50_ms"]
