"""Programs compiled, or loaded from the persistent cache, before the
window opened (jax's own backend-compile events)."""


def read(record, cell, peaks):
    return record["facts"]["warm_programs"]
