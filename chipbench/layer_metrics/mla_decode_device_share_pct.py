"""The latent decode-attention kernel's share of the device's busy time
in the traced stretch."""


def read(record, cell, peaks):
    trace = record["trace"]
    rows = trace["kernels"].get("mla_decode", {})
    if not rows or not trace["busy_s"]:
        return None
    return 100.0 * sum(r["seconds"] for r in rows.values()) / trace["busy_s"]
