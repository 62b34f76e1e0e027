"""Milliseconds a step in which the device had nothing to do while the
host was in the scheduler's own Python: self time of ``serve.step``,
``serve.sweep``, ``serve.decode`` (the ``_on_token`` loop),
``serve.admit`` and ``req.prefill``.

The account (``device_account.starved``) runs from each blocking read's
``ready`` to the start of the next span that hands the device work, over
the window; its parts and their total are logged."""
from chipbench import device_account


def read(record, cell, peaks):
    return device_account.part_ms_per_step(record, cell, "sched")
