"""Share of the WINDOW layer group's page pool that holds live sequences:
the executor's own count of that group's used pages after every chunk and
decode step of the window (its ``pages_used`` running sum, read at the
window's two ends), averaged, over the group's pool.  The pool is sized
for window + chunk a sequence, so a release that does not happen exhausts
it and fails the run; the reading says how much of that worst case the
mix uses.  A run without the counter reads nothing."""

WINDOW = 1      # the window layer group's place in the executor's sums


def read(record, cell, peaks):
    pages = record["facts"].get("pages")
    if not pages or not pages["samples"]:
        return None
    return (100.0 * pages["used"][WINDOW] / pages["samples"]
            / pages["pool"][WINDOW])
