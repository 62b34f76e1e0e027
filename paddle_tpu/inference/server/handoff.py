"""What a serving step hands the device outside its programs, counted
where it is handed over, and the one blocking read that takes a step's
result back.

The four executors share this: every array a step puts on the device
goes through :meth:`Handoff.put` (the executor's own transfer) or is
named to :meth:`Handoff.host` (a numpy argument that the program's
dispatch transfers), every device op a step runs outside a program
through a method here (:meth:`Handoff.argmax`), and ``exec.prep`` and
``exec.fetch`` carry the counts as ``h2d`` and ``eager``.  A transfer
issued beside these is in no count, which ``tests/test_obs_spans.py``
holds against: it counts ``jnp.asarray`` / ``jax.device_put`` /
``jnp.argmax`` as the executor modules call them, and the numpy leaves
their programs are handed.

``exec.fetch`` also says when the device drained.  A blocking read finds
its array READY when every program dispatched before it has finished
(one in-order stream), so from that moment (``ready``, on the tracer's
clock) to the next dispatch the device has nothing to do, whatever the
host is in.  Behind a FINAL prefill chunk the page writer is dispatched
after the chunk program and before the read: there ``ready`` is early by
at most that writer's device time (PERF.md section 3 sizes it).
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp
import numpy as np

from ... import obs


class Handoff:
    """Running counts of one executor's hand-overs; the spans record
    what was added while they were open."""

    def __init__(self):
        self.h2d = 0        # host arrays handed to the device
        self.eager = 0      # device ops dispatched outside a program

    def put(self, x, dtype=None):
        """``x`` as a device array: one transfer and, where a ``dtype``
        is asked for, the ``convert_element_type`` that ``jnp.asarray``
        then runs on the device."""
        self.h2d += 1
        if dtype is not None:
            self.eager += 1
        return jnp.asarray(x, dtype)

    def host(self, *arrays):
        """Numpy arrays the span's program takes as they are: its
        dispatch copies each to the device, one transfer an array."""
        self.h2d += len(arrays)

    def argmax(self, x, axis=None):
        """``jnp.argmax`` outside a program: one eager device op."""
        self.eager += 1
        return jnp.argmax(x, axis=axis)

    @contextlib.contextmanager
    def prep(self, **args):
        """The ``exec.prep`` span around what a step does between the
        scheduler's call and its program's dispatch; ``h2d`` and
        ``eager`` are what was handed over inside it."""
        h2d, eager = self.h2d, self.eager
        with obs.span("exec.prep", cat="serve", **args) as sp:
            yield self
            sp.set(h2d=self.h2d - h2d, eager=self.eager - eager)

    def fetch(self, what, value):
        """The step's blocking read: ``value`` (a device array or a tuple
        of them, or a function that dispatches the step's last eager ops
        and returns that) as numpy arrays.  The copy to the host is
        started, then the wait is for the arrays alone, so ``ready`` is
        the moment the device finished and not the moment the bytes
        arrived (waiting first and copying after read 0.07 ms a step
        dearer on the chip: PERF.md section 6, PR 36)."""
        with obs.span("exec.fetch", cat="serve", what=what) as sp:
            eager = self.eager
            if callable(value):
                value = value()
            arrays = value if isinstance(value, tuple) else (value,)
            for x in arrays:
                x.copy_to_host_async()
            for x in arrays:
                x.block_until_ready()
            sp.set(ready=obs.tracer().now(), eager=self.eager - eager)
            if arrays is value:
                return tuple(np.asarray(x) for x in arrays)
            return np.asarray(value)
