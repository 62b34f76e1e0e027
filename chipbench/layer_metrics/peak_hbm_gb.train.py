"""Peak device memory of the train step (the backend's own counter,
read after the window and before the reference runs)."""


def read(record, cell, peaks):
    peak = record["memory_peak_bytes"]
    return None if peak is None else peak / 1e9
