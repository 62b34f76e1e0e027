"""The paged decode-attention kernel's share of the device's busy time in
the traced stretch, in a cache of two layer groups."""


def read(record, cell, peaks):
    trace = record["trace"]
    rows = trace["kernels"].get("paged_decode_window", {})
    if not rows or not trace["busy_s"]:
        return None
    return 100.0 * sum(r["seconds"] for r in rows.values()) / trace["busy_s"]
