"""Share of the KV page pool that holds live sequences: the engine's own
count of used pages after every step of the window, averaged, over the
pool's pages."""


def read(record, cell, peaks):
    f = record["facts"]
    if f["pages_used_mean"] is None:
        return None
    return 100.0 * f["pages_used_mean"] / f["num_pages"]
