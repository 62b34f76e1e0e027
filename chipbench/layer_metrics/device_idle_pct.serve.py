"""Share of the traced window in which no operation ran on the chip:
1 - (union of the device-op intervals) / window."""


def read(record, cell, peaks):
    idle = record["trace"]["idle_share"]
    return None if idle is None else 100.0 * idle
