"""Milliseconds a step in which the device had nothing to do while the
host was inside ``exec.fetch``, from its ``ready`` to its end: the
result's copy to the host and the tail of the eager ops dispatched in
the span.  Only a step in flight while the host reads the last one
removes it.

The account (``device_account.starved``) runs from each blocking read's
``ready`` to the start of the next span that hands the device work, over
the window; its parts and their total are logged."""
from chipbench import device_account


def read(record, cell, peaks):
    return device_account.part_ms_per_step(record, cell, "copy")
