"""Milliseconds a step in which the device had nothing to do while the
host was in the data plane before its program: ``exec.prep``'s self
time (reservations, ``make_writable``, table copies, the small
transfers) and every span that is neither the read nor the scheduler's.

The account (``device_account.starved``) runs from each blocking read's
``ready`` to the start of the next span that hands the device work, over
the window; its parts and their total are logged."""
from chipbench import device_account


def read(record, cell, peaks):
    return device_account.part_ms_per_step(record, cell, "prep")
