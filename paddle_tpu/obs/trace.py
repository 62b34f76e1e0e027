"""Structured trace spans with per-request trace IDs.

Spans are host-side (name, cat, ts, dur, args, id, parent) records kept
in a bounded deque and exported as Chrome-trace JSON (``{"traceEvents":
[...]}``, timestamps in microseconds) — the format Perfetto and
``chrome://tracing`` open directly.  ``parent`` is the id of the span
that was open on the same thread when this one began, so a reader can
compute self time (a span's duration minus what its children cover).
Every live span also enters ``jax.profiler.TraceAnnotation("pt:" +
name)``: a no-op while no profiler session runs, and under one the
program's spans lie in the host planes on the profiler's clock, beside
the device's operations, to be picked out by the prefix.

One process-wide tracer (``obs.tracer()``) records always, whatever
``PT_OBS`` says; a span costs two clock reads, one small object and
one ``deque.append``.  The clock is injectable.  ``LogicalClock`` is a
deterministic auto-advancing counter so seeded tests assert exact
timestamps and durations; production uses ``time.perf_counter``.
"""
from __future__ import annotations

import itertools
import json
import threading
import time
from collections import deque

import jax

# every span's name in the profiler's host planes starts with this
ANNOTATION_PREFIX = "pt:"

# jax's own compile-pipeline durations (jax 0.9 hands each its
# ``fun_name``) -> the completed span each is recorded as
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
}


class LogicalClock:
    """Deterministic clock for seeded tests: every read advances by
    ``tick``, so the n-th read is exactly ``start + n * tick`` and any
    derived duration/percentile is a closed-form number."""

    def __init__(self, start=0.0, tick=0.001):
        self.t = float(start)
        self.tick = float(tick)
        self.reads = 0

    def __call__(self):
        self.reads += 1
        self.t += self.tick
        return self.t


class Span:
    """One completed span (``dur`` in seconds), instant (``dur`` None)
    or counter sample (``ph="C"``; ``args`` holds the series values).
    ``args`` carries structured payload — ``trace_id`` rides there so
    Perfetto shows it on every slice.  ``parent`` is the ``id`` of the
    span that was open when this one began (None at the top)."""

    __slots__ = ("name", "cat", "ts", "dur", "args", "ph", "id", "parent")

    def __init__(self, name, cat, ts, dur, args, ph=None, id=None,
                 parent=None):
        self.name = name
        self.cat = cat
        self.ts = ts
        self.dur = dur
        self.args = args
        self.ph = ph
        self.id = id
        self.parent = parent

    def __repr__(self):
        kind = ("counter" if self.ph == "C"
                else "instant" if self.dur is None
                else f"dur={self.dur:.6f}")
        return f"Span({self.name}, {kind}, args={self.args})"


class _LiveSpan:
    """Context manager handed out by :meth:`Tracer.span`; completes
    into the tracer's ring on exit.  ``set(**kv)`` attaches args only
    known mid-span (e.g. the step's loss)."""

    __slots__ = ("_tracer", "name", "cat", "args", "id", "parent",
                 "_t0", "_ann")

    def __init__(self, tracer, name, cat, args):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self.id = self.parent = None
        self._t0 = None
        self._ann = None

    def set(self, **kv):
        self.args.update(kv)
        return self

    def __enter__(self):
        tr = self._tracer
        open_ids = tr._open_ids()
        self.parent = open_ids[-1] if open_ids else None
        self.id = next(tr._ids)
        open_ids.append(self.id)
        if tr.annotate:
            self._ann = jax.profiler.TraceAnnotation(
                ANNOTATION_PREFIX + self.name)
            self._ann.__enter__()
        self._t0 = tr._clock()
        return self

    def __exit__(self, *exc):
        tr = self._tracer
        t1 = tr._clock()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        tr._open_ids().pop()
        tr._push(Span(self.name, self.cat, self._t0, t1 - self._t0,
                      self.args, id=self.id, parent=self.parent))
        return False


class Tracer:
    """Bounded span collector + Chrome-trace exporter."""

    def __init__(self, clock=time.perf_counter, capacity=65536,
                 annotate=True):
        self._ids = itertools.count(1)
        self._local = threading.local()     # .open: ids of the open spans
        self.pid = 0
        self.configure(clock, capacity, annotate)

    def configure(self, clock=time.perf_counter, capacity=65536,
                  annotate=True):
        """Swap the clock (tests: ``LogicalClock``), resize and empty the
        ring."""
        self._clock = clock
        self.capacity = int(capacity)
        self.annotate = bool(annotate)
        self.spans = deque(maxlen=self.capacity)
        self.dropped = 0

    def _open_ids(self):
        """Ids of the spans open on this thread, outermost first."""
        try:
            return self._local.open
        except AttributeError:
            self._local.open = []
            return self._local.open

    def _parent(self):
        open_ids = self._open_ids()
        return open_ids[-1] if open_ids else None

    def _push(self, span):
        if len(self.spans) == self.spans.maxlen:
            self.dropped += 1
        self.spans.append(span)

    def now(self):
        """One read of the tracer's clock, for a moment inside a span that
        the span keeps among its args (``exec.fetch``'s ``ready``)."""
        return self._clock()

    def span(self, name, cat="host", trace_id=None, **args):
        if trace_id is not None:
            args["trace_id"] = trace_id
        return _LiveSpan(self, name, cat, args)

    def instant(self, name, cat="host", trace_id=None, **args):
        if trace_id is not None:
            args["trace_id"] = trace_id
        self._push(Span(name, cat, self._clock(), None, args,
                        id=next(self._ids), parent=self._parent()))

    def complete(self, name, dur, cat="host", **args):
        """A span timed by someone else (``dur`` seconds) that ends now."""
        self._push(Span(name, cat, self._clock() - dur, dur, args,
                        id=next(self._ids), parent=self._parent()))

    def counter(self, name, cat="host", **values):
        """One counter-track sample (Chrome ``"ph": "C"``): each kwarg
        becomes a named series on the track, so Perfetto renders e.g.
        MFU / HBM-GB/s as stacked graphs above the span rows."""
        self._push(Span(name, cat, self._clock(), None, values, ph="C"))

    def listen_for_compiles(self):
        """Record jax's trace / lower / backend-compile durations as
        completed ``jit.trace`` / ``jit.lower`` / ``jit.compile`` spans
        with their ``fun_name``: which step recompiled, and what it
        cost.  An inner jit's trace lies inside its caller's, so a
        reader takes the union of the intervals, never the sum.  jax
        times them in wall seconds, so under an injected clock (tests)
        they are left out: they would read it at times no seed fixes."""
        def on_duration(event, secs, fun_name=None, **kw):
            name = _COMPILE_EVENTS.get(event)
            if name is not None and self._clock is time.perf_counter:
                self.complete(name, secs, cat="jit", fun_name=fun_name)

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    # -- export ----------------------------------------------------------

    def to_chrome_events(self):
        """Spans as Chrome-trace event dicts (ts/dur in microseconds),
        ``id``/``parent`` in ``args``.  Training spans land on tid 0,
        serving on tid 1, so the two subsystems render as separate rows
        in Perfetto."""
        events = [{"ph": "M", "name": "process_name", "pid": self.pid,
                   "tid": 0,
                   "args": {"name": "paddle_tpu host telemetry"}}]
        for tid, label in ((0, "train"), (1, "serving")):
            events.append({"ph": "M", "name": "thread_name",
                           "pid": self.pid, "tid": tid,
                           "args": {"name": label}})
        for s in self.spans:
            tid = 1 if s.cat.startswith("serve") else 0
            ev = {"name": s.name, "cat": s.cat, "pid": self.pid,
                  "tid": tid, "ts": round(s.ts * 1e6, 3),
                  "args": dict(s.args)}
            if s.ph == "C":
                ev["ph"] = "C"
            else:
                ev["args"].update(id=s.id, parent=s.parent)
                if s.dur is None:
                    ev["ph"] = "i"
                    ev["s"] = "t"  # thread-scoped instant
                else:
                    ev["ph"] = "X"
                    ev["dur"] = round(s.dur * 1e6, 3)
            events.append(ev)
        return events

    def export_chrome(self, path):
        """Write the Chrome-trace JSON; returns ``path``.  Bracketed by
        the ``obs.export`` fault point (serviceability tests inject a
        raise/crash here)."""
        from ..testing import faults

        faults.fire("obs.export", "before", path=path)
        doc = {"traceEvents": self.to_chrome_events(),
               "displayTimeUnit": "ms"}
        with open(path, "w") as f:
            json.dump(doc, f, default=str)
        faults.fire("obs.export", "after", path=path)
        return path
